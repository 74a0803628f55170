"""The deployment `fastsync-1k` and its cell (PR 31), on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

What the new files say, the arithmetic of each new reader over a hand-made
`obs` (and the None it gives a program that lacks what it reads), and a
rehearsal of the cell from its own files with the validator count cut in
a temp copy. Nothing here yields a device number.
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

CELL = "fastsync-1k.sparse"
SHAPE_READERS = ["verify.pad_lane_share", "verify.single_commit_launch_share"]
COUNTER_READERS = [
    "process.gc_pause_share", "fastsync.valset_roots_per_block", "fastsync.vote_encodes_per_block",
]


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location("m", os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce


# -- the files ------------------------------------------------------------------


def test_the_deployment_is_the_sources_and_differs_from_fastsync_100_in_the_set_alone():
    from benchmark.lib import chain

    new, old = load("configs", "fastsync-1k.json"), load("configs", "fastsync-100.json")
    assert set(new) == set(old)
    differ = {k for k in new if new[k] != old[k]}
    assert differ == {"name", "source", "deployment", "validators", "power", "assumed"}
    assert new["validators"] == 1000 and new["validators"] % 128  # off the tile: 24 pad columns
    assert chain.powers(new["power"], 1000) == [10] * 1000
    assert new["guarantees"] == old["guarantees"] and new["absent_votes"] == 0
    assert new["reduced"] == ["source_blocks"] and new["source_blocks"] == 50_000
    assert set(new["assumed"]) == {"source_blocks", "p2p_rate_bytes_per_s", "rotation", "power", "absent_votes"}
    assert "localsync.sh" in new["source"] and "1,000-validator" in new["source"] and len(new["source"]) <= 200
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    entry = next(c for c in b["configs"] if c["name"] == "fastsync-1k")
    assert entry["source"] == new["source"] and entry["reduced"] == new["reduced"]


def test_the_cell_is_the_mix_sparse_on_one_chip_and_lists_what_it_reports():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    entry = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == ("fastsync-1k", "sparse", 1)
    cell, old = load("cells", CELL + ".json"), load("cells", "fastsync-100.sparse.json")
    assert cell["chain_blocks"] == 800 and cell["trace_seconds"] == 6
    assert cell["metrics"] == ["catchup_blocks_per_s", "setup_s"]
    # everything fastsync-100.sparse reports, and what this deployment makes large (since PR 42
    # `process.gc_pause_share` is listed at 100 validators too, where the collector is 4-5% of a window)
    added = [name for name in cell["layer_metrics"] if name not in old["layer_metrics"]]
    assert added == [n for n in SHAPE_READERS + COUNTER_READERS if n != "process.gc_pause_share"]
    assert set(old["layer_metrics"]) < set(cell["layer_metrics"])
    # PR 31's five: every listed cell whose block carries 1,000 votes (since PR 42 the rotating set's too), and
    # under them `fastsync-100.sparse` for the collector's share alone
    from benchmark.tests import listing

    tree = listing.Listing()
    assert SHAPE_READERS + COUNTER_READERS == listing.PR31
    at_1k = [c for c in tree.cells if tree.deployment(c)["validators"] >= 1000]
    assert CELL in at_1k and "fastsync-100.sparse" not in at_1k
    for name in SHAPE_READERS + COUNTER_READERS:
        assert ("fastsync-100.sparse" in tree.per_layer[name]["workloads"]) == (name not in added)
        assert [c for c in tree.per_layer[name]["workloads"] if c in at_1k] == at_1k
    # the mix is shared: its file names no deployment
    assert load("traffic", "sparse.json")["reads"]["per_s"] == 20


# -- the readers ------------------------------------------------------------------


def launch(k, k_launch, n_launch, backend="tables", n=1000):
    return {"kind": "tables" if backend == "tables" else "verify", "t": 1001.0, "backend": backend,
            "height_lo": 10, "height_hi": 10 + k - 1, "rows": k * n, "k_launch": k_launch,
            "n_launch": n_launch, "rows_padded": k_launch * n_launch - k * n}


def test_the_launch_shape_readers_over_hand_made_records():
    recs = [
        launch(1, 1, 1024), launch(1, 1, 1024), launch(16, 16, 1024), launch(5, 16, 1024),
        # a 70-commit walk: chunks of 64 and 16, k_launch summed
        launch(70, 80, 1024),
        # not the device's, not a window's, or failed: left out
        launch(3, 3, 100, backend="host", n=100),
        {**launch(2, 16, 1024), "height_lo": None}, {**launch(2, 16, 1024), "error": "Boom"},
    ]
    obs = {"launches": recs}
    lanes = (1 + 1 + 16 + 16 + 80) * 1024
    assert reader("verify.pad_lane_share")(obs) == pytest.approx(100 * (lanes - 93_000) / lanes)
    assert reader("verify.single_commit_launch_share")(obs) == pytest.approx(100 * 2 / 5)
    # 1,000 validators, full windows only: 24 of 1,024 columns
    full = {"launches": [launch(16, 16, 1024)] * 3}
    assert reader("verify.pad_lane_share")(full) == pytest.approx(100 * 24 / 1024)
    # no launch of one commit: a share of 0, not None
    assert reader("verify.single_commit_launch_share")(full) == 0.0


@pytest.mark.parametrize("name", SHAPE_READERS)
def test_a_launch_shape_reader_finds_nothing_without_shaped_device_launches(name):
    # a program from before PR 27: records without k_launch and n_launch
    bare = {k: v for k, v in launch(16, 16, 1024).items() if k not in ("k_launch", "n_launch", "rows_padded")}
    assert reader(name)({"launches": [bare]}) is None
    # every window on the host library, or no window at all
    assert reader(name)({"launches": [launch(3, 3, 100, backend="host", n=100)]}) is None
    assert reader(name)({"launches": []}) is None


def pull(**series):
    from benchmark.lib import rpc

    return rpc.parse_metrics("".join(f"{k} {v!r}\n" for k, v in series.items()))


def test_the_counter_readers_over_a_hand_made_pair_of_pulls():
    start = pull(tendermint_fastsync_blocks_applied_total=48, tendermint_valset_hashes_total=1,
                 tendermint_vote_encodes_total=48_000, tendermint_process_gc_pause_seconds_sum=0.5)
    end = pull(tendermint_fastsync_blocks_applied_total=408, tendermint_valset_hashes_total=2,
               tendermint_vote_encodes_total=408_000, tendermint_process_gc_pause_seconds_sum=3.5)
    obs = {"metrics_start": start, "metrics_end": end, "window": [1000.0, 1030.0]}
    assert reader("fastsync.valset_roots_per_block")(obs) == pytest.approx(1 / 360)
    assert reader("fastsync.vote_encodes_per_block")(obs) == pytest.approx(1000.0)
    assert reader("process.gc_pause_share")(obs) == pytest.approx(10.0)
    # a counter that stood still reads 0, not None
    obs["metrics_end"] = {**end, "tendermint_valset_hashes_total": [({}, 1.0)]}
    assert reader("fastsync.valset_roots_per_block")(obs) == 0.0


@pytest.mark.parametrize("name", COUNTER_READERS)
def test_a_counter_reader_gives_none_on_a_program_without_its_series(name):
    older_start = pull(tendermint_fastsync_blocks_applied_total=48)
    older_end = pull(tendermint_fastsync_blocks_applied_total=408)
    obs = {"metrics_start": older_start, "metrics_end": older_end, "window": [1000.0, 1030.0]}
    assert reader(name)(obs) is None
    if name.startswith("fastsync."):
        # ... and where no block was applied there is nothing to divide by
        same = pull(tendermint_fastsync_blocks_applied_total=48, tendermint_valset_hashes_total=1,
                    tendermint_vote_encodes_total=48_000)
        assert reader(name)({"metrics_start": same, "metrics_end": same, "window": [0.0, 30.0]}) is None


def test_each_new_reader_has_the_contracts_entry_beside_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in SHAPE_READERS + COUNTER_READERS:
        meta = load("layer_metrics", name + ".json")
        assert meta["name"] == name and meta["what"]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert meta[key] == per_layer[name][key]


# -- the cell, from its own files, at a validator count the CPU can sign --------------


def test_the_cell_rehearsed_from_its_own_files_with_the_validator_count_cut(tmp_path):
    """`fastsync-1k.sparse` as the driver runs it, traced, but for
    `validators`: 13 in the temp copy's configuration (uniform power, off
    every tile; 32 commits of 13 stay under the 512 lanes from which a
    launch is the device's to answer, which the CPU cannot)."""
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.ignore_patterns("out", "cache", "__pycache__"))
    os.symlink(os.path.join(ROOT, "tendermint_tpu"), tmp_path / "tendermint_tpu")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    cfg = load("configs", "fastsync-1k.json")
    cfg["validators"] = 13
    with open(tmp_path / "benchmark" / "configs" / "fastsync-1k.json", "w") as f:
        json.dump(cfg, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "3100000007",
         "--seconds", "3", "--trace", "1", "--allow-cpu-for-tests"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode in (0, 1), proc.stdout[-3000:] + proc.stderr[-3000:]
    # (nothing may be wrong: the last-write check, which raced the apply in about one
    # tiny CPU run in ten, asks at the app's own height since PR 42)
    wrong = [row for row in proc.stdout.splitlines() if "NOT CORRECT" in row]
    assert not wrong, wrong
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    got = line["metrics"]
    assert got["verify.host_fallbacks"]["value"] == 0.0
    # the peer sends canonical bytes and a decoded vote keeps them (PR 33): no
    # vote is encoded again; the set's root is kept
    assert got["fastsync.vote_encodes_per_block"] == {"value": pytest.approx(0.0, abs=0.5), "unit": "encodes/block"}
    assert got["fastsync.valset_roots_per_block"]["value"] < 0.05
    assert 0 <= got["process.gc_pause_share"]["value"] < 50
    # no device answered on the CPU: the launch-shape and the trace readers find nothing
    assert not set(SHAPE_READERS + ["device.idle_share", "kernel.verify_us_per_sig"]) & set(got)
    detail = json.load(open(tmp_path / "benchmark" / "out" / f"{CELL}-3100000007.json"))
    assert detail["notes"]["heights"][0] >= 48  # the mix's warm_blocks, untouched
