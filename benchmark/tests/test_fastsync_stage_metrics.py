"""The readers of fast-sync's stage clock (PR 24), on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

Their arithmetic over a hand-made pair of `/metrics` pulls, what they give
a program that has no such series, and a traced run of a tiny cell that
lists them. Every cell of `BENCHMARK.json` lists them since PR 35.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

STAGES = ("decode", "part_set", "verify_submit", "verify_wait", "store", "validate", "exec", "state_save")
NEW = [f"fastsync.{s}_ms_per_block" for s in STAGES] + [
    "fastsync.starved_share", "fastsync.accounted_share", "fastsync.full_window_share",
]


def reader(name):
    spec = importlib.util.spec_from_file_location("m", os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce


def pull(blocks: int, seconds: dict, cuts: dict) -> dict:
    """A `/metrics` answer as the node words it, parsed as the driver does."""
    from benchmark.lib import rpc

    lines = ["# TYPE tendermint_fastsync_stage_seconds histogram"]
    for stage, s in seconds.items():
        lines.append(f'tendermint_fastsync_stage_seconds_bucket{{stage="{stage}",le="+Inf"}} 7')
        lines.append(f'tendermint_fastsync_stage_seconds_sum{{stage="{stage}"}} {s!r}')
        lines.append(f'tendermint_fastsync_stage_seconds_count{{stage="{stage}"}} 7')
    lines.append(f"tendermint_fastsync_blocks_applied_total {blocks}")
    lines += [f'tendermint_fastsync_windows_total{{cut="{c}"}} {n}' for c, n in cuts.items()]
    return rpc.parse_metrics("\n".join(lines) + "\n")


def test_the_readers_over_a_hand_made_pair_of_pulls():
    start = pull(
        100, {s: 1.0 for s in (*STAGES, "starved")}, {"full": 5, "pool_gap": 5, "boundary": 0}
    )
    rose = {"decode": 3.0, "part_set": 2.0, "verify_submit": 4.0, "verify_wait": 1.0, "store": 2.5,
            "validate": 5.0, "exec": 1.0, "state_save": 2.0, "starved": 0.5}
    end = pull(
        1100, {s: 1.0 + v for s, v in rose.items()}, {"full": 65, "pool_gap": 44, "boundary": 1}
    )
    obs = {"metrics_start": start, "metrics_end": end, "window": [1000.0, 1030.0]}
    # 1,000 blocks in a 30 s window: a stage's seconds are its ms a block
    for stage in STAGES:
        assert reader(f"fastsync.{stage}_ms_per_block")(obs) == pytest.approx(rose[stage])
    assert reader("fastsync.starved_share")(obs) == pytest.approx(100 * 0.5 / 30)
    # every sync-thread stage, starved with them, decode (another thread's) left out
    assert reader("fastsync.accounted_share")(obs) == pytest.approx(100 * (sum(rose.values()) - 3.0) / 30)
    assert reader("fastsync.full_window_share")(obs) == pytest.approx(100 * 60 / 100)


def test_no_block_applied_is_nothing_to_read():
    same = pull(100, {s: 1.0 for s in (*STAGES, "starved")}, {"full": 5, "pool_gap": 5, "boundary": 0})
    obs = {"metrics_start": same, "metrics_end": same, "window": [1000.0, 1030.0]}
    assert [reader(name)(obs) for name in NEW] == [None] * len(NEW)
    # a program from before the stage clock has no such series at all
    older = {"tendermint_xla_compile_seconds_count": [({}, 12.0)]}
    obs = {"metrics_start": older, "metrics_end": older, "window": [1000.0, 1030.0]}
    assert [reader(name)(obs) for name in NEW] == [None] * len(NEW)


def test_each_reader_has_the_contracts_entry_beside_it():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    known = {m["name"]: m for m in bench["per_layer"]}
    cells = {
        w["name"]: json.load(open(os.path.join(BENCH, "cells", w["name"] + ".json")))["layer_metrics"]
        for w in bench["workloads"]
    }
    for name in NEW:
        meta = json.load(open(os.path.join(BENCH, "layer_metrics", name + ".json")))
        assert meta["name"] == name and meta["layer"] == "fast-sync" and meta["what"]
        assert meta["source"] == "program_counter" and meta["moves"] == "catchup_blocks_per_s"
        assert (meta["unit"], meta["better"]) in (("ms", "lower"), ("%", "lower"), ("%", "higher"))
        assert len(name) <= 64
        # in BENCHMARK.json, and in every cell that lists it: there, under `workloads`, and nowhere else
        listing = [cell for cell, names in cells.items() if name in names]
        assert listing and known[name]["workloads"] == listing
        for key in ("unit", "better", "source", "layer", "moves"):
            assert known[name][key] == meta[key]


def test_a_traced_tiny_cell_reports_every_stage_metric(tmp_path):
    """New files alone: a 16-validator deployment whose cell lists the
    eleven readers, run as the driver runs a cell."""
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.ignore_patterns("out", "cache", "__pycache__"))
    os.symlink(os.path.join(ROOT, "tendermint_tpu"), tmp_path / "tendermint_tpu")
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cfg = json.load(open(os.path.join(BENCH, "configs", "fastsync-100.json")))
    # 15 signers x 16 commits: two windows merged into one launch stay under
    # the 512 lanes from which a launch is the device's to answer
    cfg.update(name="tiny16", validators=16, absent_votes=1)
    json.dump(cfg, open(tmp_path / "benchmark" / "configs" / "tiny16.json", "w"))
    b["configs"].append({"name": "tiny16", "source": "test", "file": "benchmark/configs/tiny16.json", "reduced": [], "why": "test"})
    b["workloads"].append({"name": "tiny16.trickle", "config": "tiny16", "traffic": "trickle", "chips": 1, "why": "test"})
    json.dump(
        {"chain_blocks": 1200, "metrics": ["catchup_blocks_per_s", "setup_s"], "layer_metrics": ["verify.host_fallbacks", *NEW]},
        open(tmp_path / "benchmark" / "cells" / "tiny16.trickle.json", "w"),
    )
    json.dump(
        {"name": "trickle", "driver": "catchup", "txs": {"kind": "fresh_keys", "per_block": 2},
         "reads": {"kinds": ["status"], "per_s": 10}, "warm_blocks": 8},
        open(tmp_path / "benchmark" / "traffic" / "trickle.json", "w"),
    )
    json.dump(b, open(tmp_path / "BENCHMARK.json", "w"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny16.trickle", "--seed", "3000000019",
         "--seconds", "2", "--trace", "1", "--allow-cpu-for-tests"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode in (0, 1), proc.stdout[-3000:] + proc.stderr[-3000:]
    # (nothing may be wrong: the last-write check, which raced the apply in about one
    # tiny CPU run in ten, asks at the app's own height since PR 42)
    wrong = [row for row in proc.stdout.splitlines() if "NOT CORRECT" in row]
    assert not wrong, wrong
    got = json.loads(proc.stdout.strip().splitlines()[-1])["metrics"]
    assert set(NEW) <= set(got)
    for stage in STAGES:
        assert got[f"fastsync.{stage}_ms_per_block"]["value"] > 0 and got[f"fastsync.{stage}_ms_per_block"]["unit"] == "ms"
    assert 0 <= got["fastsync.starved_share"]["value"] < 100
    # the closing /metrics pull ends a little after the window's clock does,
    # which in a 2 s window is a few percent
    assert 50 < got["fastsync.accounted_share"]["value"] < 110
    assert 0 <= got["fastsync.full_window_share"]["value"] <= 100
