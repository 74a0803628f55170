"""The readers of the second clock (PR 38), on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

Their arithmetic over a hand-made pair of `/metrics` pulls, what they give
a program that has no such series (the parent of PR 38), the meta files
against the entries `withheld/own_work.json` held ready for
`BENCHMARK.json`, the four accepted cells' lists as PR 42 (`benchmark`)
left them (data of record, found by name; what any listed cell keeps is
`listing.py`'s), and a traced run of a tiny cell that lists all seventeen.

PR 38 (`tracing`) could list them in no cell: it may edit no file the
benchmark already has, and `run.py::find_cell` takes a cell's readers
from `cells/<cell>.json`. `listed()` below is the edit PR 42 made, and
the cells' files are held to it.
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

from benchmark.tests import listing  # noqa: E402
from benchmark.tests.listing import HASH_READERS, PR31, VERIFY_KERNEL  # noqa: E402

# file pairs only until PR 42: the table build's beside PR 36's three,
# listed where the set changes and nowhere else (a static set builds none inside
# a window: a reader with nothing to read in a cell is not listed there), and
# the reader of the answers' bytes, which REVIEW.md asked for after ISSUE 38
# had fixed the seventeen, listed in every cell behind them
LISTED_LATER = ["verify.table_build_ms", "entry.block_answer_bytes"]
BUILD, ANSWER = LISTED_LATER


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


OWN = load("tests", "withheld", "own_work.json")
NEW = OWN["layer_metrics"]


def reader(name):
    spec = importlib.util.spec_from_file_location("m", os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce


def listed(cell: str) -> list[str]:
    """`cells/<cell>.json`'s `layer_metrics` with the seventeen and the
    answers' bytes appended: at the end, and in a cell that lists the four
    hash readers before those, where `fastsync-1k.sparse`'s order puts them."""
    names = [n for n in load("cells", cell + ".json")["layer_metrics"] if n not in [*NEW, ANSWER]]
    tail = [n for n in names if n in HASH_READERS]
    return [n for n in names if n not in tail] + NEW + [ANSWER] + tail


# -- the lists -------------------------------------------------------------------


def test_there_are_seventeen_each_a_file_pair_and_an_entry_ready_for_the_contract():
    assert len(NEW) == 17 == len(set(NEW)) and not set(LISTED_LATER) & set(NEW)
    tree = listing.Listing()
    known = tree.per_layer
    layers = {m["layer"] for m in known.values()}
    ready = {m["name"]: m for m in OWN["per_layer"]}
    assert list(ready) == NEW
    for name in [*NEW, *LISTED_LATER]:
        meta = load("layer_metrics", name + ".json")
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))
        assert meta["name"] == name and len(name) <= 64 and meta["what"]
        assert meta["layer"] in layers and meta["layer"] == {
            "fastsync": "fast-sync", "entry": "entry", "process": "process", "verify": "verify spine",
        }[name.split(".")[0]]
        assert meta["source"] == "program_counter" and meta["moves"] == "catchup_blocks_per_s"
        assert (meta["unit"], meta["better"]) in (
            ("ms", "lower"), ("%", "lower"), ("%", "higher"), ("reads", "lower"), ("bytes", "lower"),
        )
        # BENCHMARK.json has the entry, and for the seventeen it is the one held ready, with the cells
        # whose file lists the reader: every listed cell, but for the table build's, which a changing set's list
        entry = known[name]
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (name in LISTED_LATER and name not in ready) or {k: v for k, v in entry.items() if k != "workloads"} == ready[name]
        assert entry["workloads"] == ([c for c in tree.cells if "valset" in tree.mix(c)] if name == BUILD else tree.cells)
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert entry[key] == meta[key]


def test_the_four_accepted_cells_list_the_seventeen_in_one_order_and_the_old_pins_hold():
    accepted = {"small": "fastsync-100.sparse", "sparse": "fastsync-1k.sparse", "full": "fastsync-1k.full", "rotate": "valchange-1k.rotate"}
    lists = {cell: load("cells", cell + ".json")["layer_metrics"] for cell in accepted.values()}
    for cell, names in lists.items():
        # the cell's file is the edit `listed()` describes
        assert names == listed(cell)
        assert [n for n in names if n in NEW] == NEW and len(set(names)) == len(names)
    small, sparse, full, rotate = (lists[accepted[k]] for k in ("small", "sparse", "full", "rotate"))
    # test_fastsync_1k.py: what `fastsync-1k.sparse` lists beyond `fastsync-100.sparse` is PR 31's five,
    # less the collector's share, which `fastsync-100.sparse` lists too since PR 42
    assert [n for n in sparse if n not in small] == [n for n in PR31 if n != "process.gc_pause_share"]
    assert set(small) < set(sparse) and "process.gc_pause_share" in small
    # test_fastsync_1k_full.py: `.full` is `.sparse` less the verify kernel's two, then the hash readers
    assert full == [n for n in sparse if n not in VERIFY_KERNEL] + HASH_READERS
    # test_valchange_1k_cell.py: the rotating set's cell lists the table build's reader, and no static set's does
    assert BUILD in rotate and not any(BUILD in names for names in (small, sparse, full))
    assert small[-18:] == [*NEW, ANSWER] == sparse[-18:] == rotate[-18:] and full[-22:-4] == [*NEW, ANSWER]


# -- the arithmetic ----------------------------------------------------------------


def pull(blocks, stages, commits, phases, process_cpu, threads, builds=None, answered=None) -> dict:
    """A `/metrics` answer as the node words it, parsed as the driver does.
    `stages`: {stage: (seconds, cpu)}; `commits`: {db: (count, seconds, cpu)};
    `phases`: {(method, phase): (count, seconds, cpu)}; `builds`: {kind: (count, seconds)};
    `answered`: {method: bytes}."""
    from benchmark.lib import rpc

    lines = [f"tendermint_fastsync_blocks_applied_total {blocks}"]
    for stage, (s, cpu) in stages.items():
        lines.append(f'tendermint_fastsync_stage_seconds_sum{{stage="{stage}"}} {s!r}')
        lines.append(f'tendermint_fastsync_stage_seconds_count{{stage="{stage}"}} 7')
        lines.append(f'tendermint_fastsync_stage_cpu_seconds_total{{stage="{stage}"}} {cpu!r}')
    for db, (n, s, cpu) in commits.items():
        lines.append(f'tendermint_db_commits_total{{db="{db}"}} {n}')
        lines.append(f'tendermint_db_commit_seconds_sum{{db="{db}"}} {s!r}')
        lines.append(f'tendermint_db_commit_seconds_count{{db="{db}"}} {n}')
        lines.append(f'tendermint_db_commit_cpu_seconds_total{{db="{db}"}} {cpu!r}')
    for (method, phase), (n, s, cpu) in phases.items():
        ls = f'method="{method}",phase="{phase}"'
        lines.append(f"tendermint_rpc_phase_seconds_sum{{{ls}}} {s!r}")
        lines.append(f"tendermint_rpc_phase_seconds_count{{{ls}}} {n}")
        lines.append(f"tendermint_rpc_phase_cpu_seconds_total{{{ls}}} {cpu!r}")
    lines.append(f"tendermint_process_cpu_seconds_total {process_cpu!r}")
    lines += [f'tendermint_process_thread_cpu_seconds{{thread="{t}"}} {v!r}' for t, v in threads.items()]
    for kind, (n, s) in (builds or {}).items():
        lines.append(f'tendermint_verify_table_build_seconds_sum{{kind="{kind}"}} {s!r}')
        lines.append(f'tendermint_verify_table_build_seconds_count{{kind="{kind}"}} {n}')
    lines += [f'tendermint_rpc_response_bytes_total{{method="{m}"}} {n}' for m, n in (answered or {}).items()]
    return rpc.parse_metrics("\n".join(lines) + "\n")


STAGES = ("decode", "part_set", "verify_submit", "verify_wait", "store", "validate", "exec", "state_save",
          "starved", "index_rows")


def hand_made() -> dict:
    start = pull(
        100, {s: (1.0, 0.5) for s in STAGES},
        {"blockstore": (100, 0.5, 0.1), "state": (200, 1.0, 0.2), "txindex": (100, 0.5, 0.1)},
        {("block", p): (10, 1.0, 0.5) for p in ("parse", "handle", "encode", "write", "load", "render")}
        | {("status", p): (10, 0.1, 0.1) for p in ("parse", "handle", "encode", "write")},
        50.0, {"fastsync": 20.0, "rpc": 5.0, "p2p_recv": 4.0, "other": 1.0},
        {"full": (1, 19.0), "incremental": (0, 0.0)}, {"block": 9_770, "status": 5_000},
    )
    # 1,000 blocks in a 30 s window: a stage's seconds are its ms a block
    wall = {"decode": 3.0, "part_set": 4.0, "verify_submit": 1.0, "verify_wait": 0.5, "store": 5.0,
            "validate": 2.0, "exec": 3.0, "state_save": 8.0, "starved": 0.25, "index_rows": 2.0}
    cpu = {"decode": 1.5, "part_set": 0.5, "verify_submit": 0.75, "verify_wait": 0.25, "store": 2.0,
           "validate": 1.0, "exec": 2.5, "state_save": 4.0, "starved": 0.0, "index_rows": 1.75}
    end = pull(
        1100, {s: (1.0 + wall[s], 0.5 + cpu[s]) for s in STAGES},
        # 4.00 commits a block: 2.5 s asleep on them, 0.5 s of C inside them
        {"blockstore": (1100, 0.5 + 1.0, 0.1 + 0.125), "state": (2200, 1.0 + 1.5, 0.2 + 0.25),
         "txindex": (1100, 0.5 + 0.5, 0.1 + 0.125)},
        # 100 /block reads: 60 s of top phases, 9 s of CPU; inside handle, load 20 s and render 15 s
        {("block", "parse"): (110, 1.0 + 1.0, 0.5 + 0.5), ("block", "handle"): (110, 1.0 + 40.0, 0.5 + 6.0),
         ("block", "encode"): (110, 1.0 + 10.0, 0.5 + 2.0), ("block", "write"): (110, 1.0 + 9.0, 0.5 + 0.5),
         ("block", "load"): (110, 1.0 + 20.0, 0.5 + 3.0), ("block", "render"): (110, 1.0 + 15.0, 0.5 + 2.5),
         # 300 /status reads: 3 s, all of it run
         ("status", "parse"): (310, 0.1 + 0.5, 0.1 + 0.5), ("status", "handle"): (310, 0.1 + 1.5, 0.1 + 1.5),
         ("status", "encode"): (310, 0.1 + 0.5, 0.1 + 0.5), ("status", "write"): (310, 0.1 + 0.5, 0.1 + 0.5)},
        50.0 + 33.0, {"fastsync": 20.0 + 15.0, "rpc": 5.0 + 12.0, "p2p_recv": 4.0 + 3.0, "other": 1.0 + 1.5},
        {"full": (1, 19.0), "incremental": (4, 0.0 + 2.0)},
        # the 100 /block answers: 337,646 bytes each
        {"block": 9_770 + 33_764_600, "status": 5_000 + 150_000},
    )
    return {"metrics_start": start, "metrics_end": end, "window": [1000.0, 1030.0]}


def test_the_readers_over_a_hand_made_pair_of_pulls():
    obs = hand_made()
    got = {name: reader(name)(obs) for name in [*NEW, *LISTED_LATER]}
    approx = pytest.approx
    # the sync thread's stages but starved, decode (the p2p thread's) left out
    assert got["fastsync.cpu_ms_per_block"] == approx(0.5 + 0.75 + 0.25 + 2.0 + 1.0 + 2.5 + 4.0)
    assert got["fastsync.disk_ms_per_block"] == approx(1.0 + 1.5 + 0.5)
    # part_set, verify_submit, store, exec, state_save: wall 21, CPU 9.75; the commits 3.0 less 0.5
    assert got["fastsync.lock_wait_ms_per_block"] == approx(21.0 - 9.75 - (3.0 - 0.5))
    assert got["fastsync.part_set_cpu_ms_per_block"] == approx(0.5)
    assert got["fastsync.store_cpu_ms_per_block"] == approx(2.0)
    assert got["fastsync.state_save_cpu_ms_per_block"] == approx(4.0)
    assert got["fastsync.decode_cpu_ms_per_block"] == approx(1.5)
    assert got["fastsync.index_rows_ms_per_block"] == approx(2.0)
    assert got["entry.block_server_ms"] == approx(1e3 * 60.0 / 100)
    assert got["entry.block_load_ms"] == approx(1e3 * 20.0 / 100)
    assert got["entry.block_render_encode_ms"] == approx(1e3 * (15.0 + 10.0) / 100)
    # every method, the four top phases: 12 s run of 63 s
    assert got["entry.rpc_running_share"] == approx(100 * (9.0 + 3.0) / 63.0)
    assert got["entry.rpc_mean_in_flight"] == approx(63.0 / 30)
    assert got["process.cpu_busy_share"] == approx(100 * 33.0 / 30)
    assert got["process.sync_thread_cpu_share"] == approx(100 * 15.0 / 30)
    assert got["process.rpc_cpu_share"] == approx(100 * 12.0 / 30)
    assert got["process.p2p_recv_cpu_share"] == approx(100 * 3.0 / 30)
    assert got["process.cpu_busy_share"] >= sum(
        got[n] for n in ("process.sync_thread_cpu_share", "process.rpc_cpu_share", "process.p2p_recv_cpu_share")
    )
    # four incremental builds ended in the window, 2 s together
    assert got[BUILD] == approx(1e3 * 2.0 / 4)
    assert got[ANSWER] == approx(337_646)
    # the identity PERF.md shows for a traced run of each cell
    from benchmark.lib import own_work

    parts = own_work.lock_wait_parts(obs)
    assert parts["wall"] == approx(parts["cpu"] + (parts["commit_wall"] - parts["commit_cpu"]) + parts["lock_wait"])
    assert parts["lock_wait"] >= 0


def test_a_program_without_the_series_gives_nothing_to_read():
    # the parent of PR 38: the stage clock, the commits' counter, and no more
    from benchmark.lib import rpc

    def older(blocks):
        lines = [f"tendermint_fastsync_blocks_applied_total {blocks}"]
        for s in STAGES[:-1]:
            lines.append(f'tendermint_fastsync_stage_seconds_sum{{stage="{s}"}} {blocks / 100}')
        lines.append(f'tendermint_db_commits_total{{db="state"}} {2 * blocks}')
        lines.append('tendermint_rpc_request_seconds_sum{method="block"} 1.0')
        return rpc.parse_metrics("\n".join(lines) + "\n")

    obs = {"metrics_start": older(100), "metrics_end": older(1100), "window": [1000.0, 1030.0]}
    assert [reader(name)(obs) for name in [*NEW, *LISTED_LATER]] == [None] * 19
    # the series there, and no block applied, no /block read, no build: nothing a block or a read
    same = hand_made()["metrics_end"]
    obs = {"metrics_start": same, "metrics_end": same, "window": [1000.0, 1030.0]}
    per_unit = [n for n in NEW if n.startswith("fastsync.") or n.startswith("entry.block_")] + [
        "entry.rpc_running_share", *LISTED_LATER,
    ]
    assert [reader(name)(obs) for name in per_unit] == [None] * len(per_unit)


# -- a traced run ------------------------------------------------------------------


def test_a_traced_tiny_cell_reports_all_seventeen(tmp_path):
    """New files alone: a 16-validator deployment whose cell lists the
    seventeen readers and the stage clock's own, run as the driver runs a
    cell (the mix reads `/block` too: three of the readers are its)."""
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.ignore_patterns("out", "cache", "__pycache__"))
    os.symlink(os.path.join(ROOT, "tendermint_tpu"), tmp_path / "tendermint_tpu")
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cfg = load("configs", "fastsync-100.json")
    # 15 signers x 16 commits: two windows merged into one launch stay under
    # the 512 lanes from which a launch is the device's to answer
    cfg.update(name="tiny16", validators=16, absent_votes=1)
    json.dump(cfg, open(tmp_path / "benchmark" / "configs" / "tiny16.json", "w"))
    b["configs"].append({"name": "tiny16", "source": "test", "file": "benchmark/configs/tiny16.json", "reduced": [], "why": "test"})
    b["workloads"].append({"name": "tiny16.trickle", "config": "tiny16", "traffic": "trickle", "chips": 1, "why": "test"})
    wall = [f"fastsync.{s}_ms_per_block" for s in ("part_set", "store", "state_save", "decode", "exec", "verify_submit")]
    json.dump(
        {"chain_blocks": 1200, "metrics": ["catchup_blocks_per_s", "setup_s"],
         "layer_metrics": ["verify.host_fallbacks", "fastsync.accounted_share", *wall, *NEW, *LISTED_LATER]},
        open(tmp_path / "benchmark" / "cells" / "tiny16.trickle.json", "w"),
    )
    json.dump(
        {"name": "trickle", "driver": "catchup", "txs": {"kind": "fresh_keys", "per_block": 2},
         "reads": {"kinds": ["status", "block"], "per_s": 10}, "warm_blocks": 8},
        open(tmp_path / "benchmark" / "traffic" / "trickle.json", "w"),
    )
    json.dump(b, open(tmp_path / "BENCHMARK.json", "w"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny16.trickle", "--seed", "3800000019",
         "--seconds", "2", "--trace", "1", "--allow-cpu-for-tests"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode in (0, 1), proc.stdout[-3000:] + proc.stderr[-3000:]
    # (nothing may be wrong: the last-write check, which raced the apply in about one
    # tiny CPU run in ten, asks at the app's own height since PR 42)
    wrong = [row for row in proc.stdout.splitlines() if "NOT CORRECT" in row]
    assert not wrong, wrong
    got = {k: v["value"] for k, v in json.loads(proc.stdout.strip().splitlines()[-1])["metrics"].items()}
    assert set(NEW) <= set(got), sorted(set(NEW) - set(got))
    assert BUILD not in got  # a static set: no build ends inside a window
    assert 500 < got[ANSWER] < 5_000  # a /block of two txs and 15 precommits
    # CPU under wall, stage by stage, on the same stretches: give or take
    # the reading a stage may share with the boundary before it (0.1 ms,
    # `tracer.CPU_SHARE_NS`) and, where the thread clock is coarse, its steps
    for stage in ("part_set", "store", "state_save", "decode"):
        assert 0 <= got[f"fastsync.{stage}_cpu_ms_per_block"] <= got[f"fastsync.{stage}_ms_per_block"] + 0.25, stage
    # what the thread ran is at most what its stages lasted (2 s window: the closing pull ends a little late)
    lasted = sum(got[n] for n in wall if "decode" not in n)
    assert 0 < got["fastsync.cpu_ms_per_block"] and got["fastsync.part_set_cpu_ms_per_block"] < lasted
    assert got["fastsync.disk_ms_per_block"] > 0
    assert got["fastsync.lock_wait_ms_per_block"] > -0.05
    assert 0 <= got["fastsync.index_rows_ms_per_block"] < got["fastsync.state_save_ms_per_block"]
    assert got["entry.block_load_ms"] + got["entry.block_render_encode_ms"] <= got["entry.block_server_ms"] * 1.001
    assert 0 < got["entry.rpc_running_share"] <= 100
    assert 0 < got["entry.rpc_mean_in_flight"] <= 32
    shares = [got[f"process.{t}_cpu_share"] for t in ("sync_thread", "rpc", "p2p_recv")]
    assert all(s > 0 for s in shares) and got["process.cpu_busy_share"] >= sum(shares) * 0.98
