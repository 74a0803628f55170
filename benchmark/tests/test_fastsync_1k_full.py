"""The tx rules as files, the mix `full` and the cell `fastsync-1k.full`
(PR 35), on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

What the rules write, that a `fresh_keys` chain is the chain it was, the
arithmetic of the hash layer's new readers over a hand-made `obs`, and
rehearsals of the cell from its own files with its scale cut in a temp
copy. Nothing here yields a device number.
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
from benchmark.tests.listing import HASH_READERS, VERIFY_KERNEL  # noqa: E402

CELL = "fastsync-1k.full"


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location("m", os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce


# -- the tx rules -------------------------------------------------------------------


def test_a_fresh_keys_chain_is_the_chain_it_was(tmp_path):
    """`fresh_keys` moved into a file of its own unchanged: the two
    accepted cells sync the same bytes (the digest, the last app hash and
    the last data hash of this chain as the tree before PR 35 built it)."""
    from benchmark.lib import chain

    config = load("configs", "fastsync-100.json")
    config["validators"] = 7
    rec = chain.build_chain(config, load("traffic", "sparse.json"), seed=2147483659, n_blocks=24,
                            home=str(tmp_path / "home"), workers=0)
    assert chain.digest(rec) == "dfce3a2f2465dc11"
    assert rec.app_hash[-1][:16] == "44ead0aecad34abb" and rec.data_hash[-1][:16] == "c3b4a7bd3c7ddaec"
    assert chain.block_txs({"txs": {"kind": "fresh_keys", "per_block": 3}}, 5) == [
        b"h0000005-0=35", b"h0000005-1=36", b"h0000005-2=37",
    ]


def test_fixed_keys_writes_every_key_once_a_block_and_wraps_under_it():
    from benchmark.lib import chain

    def block(keys, n, h):
        return [tx.split(b"=") for tx in chain.block_txs({"txs": {"kind": "fixed_keys", "keys": keys, "per_block": n}}, h)]

    # K = n: every key once a block, whatever the height
    for h in (1, 2, 77):
        txs = block(100, 100, h)
        assert sorted(k for k, _v in txs) == [b"k%07d" % i for i in range(100)]
    # no two txs of a chain are the same bytes, and a block's values differ from the last block's
    seen = [v for h in range(1, 6) for _k, v in block(100, 100, h)]
    assert len(set(seen)) == len(seen)
    assert block(100, 100, 3)[0] == [b"k0000000", b"300"]
    # K < n wraps: 10 keys, 25 txs, the block starts where the last one ended
    txs = block(10, 25, 3)
    assert [k for k, _v in txs] == [b"k%07d" % ((75 + i) % 10) for i in range(25)]
    assert {k for k, _v in txs} == {b"k%07d" % i for i in range(10)}
    # the block's last tx is the last write of its key: what check_last_write reads back
    last_key, last_value = txs[-1]
    assert [v for k, v in txs if k == last_key][-1] == last_value == b"99"
    # the mix's own sizes: 14 to 16 bytes at 10,000 a block, one SHA-256 block a leaf
    mix = load("traffic", "full.json")
    sizes = {len(tx) for h in (1, 99, 100, 400) for tx in chain.block_txs(mix, h)}
    assert sizes <= {14, 15, 16} and len(chain.block_txs(mix, 7)) == 10_000


def test_an_unknown_tx_rule_names_the_file_it_looked_for():
    from benchmark.lib import chain

    with pytest.raises(ValueError) as e:
        chain.block_txs({"txs": {"kind": "no_such_rule", "per_block": 3}}, 1)
    assert os.path.join(BENCH, "tx_rules", "no_such_rule.py") in str(e.value)
    # every mix's rule is a file
    for name in os.listdir(os.path.join(BENCH, "traffic")):
        kind = load("traffic", name)["txs"]["kind"]
        assert os.path.isfile(os.path.join(BENCH, "tx_rules", kind + ".py"))


# -- the files ------------------------------------------------------------------------


def test_the_cell_is_the_mix_full_on_one_chip_and_lists_what_it_reports():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    entry = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == ("fastsync-1k", "full", 1)
    mix, sparse = load("traffic", "full.json"), load("traffic", "sparse.json")
    assert mix["txs"] == {"kind": "fixed_keys", "keys": 10_000, "per_block": 10_000}
    assert mix["txs"]["per_block"] == load("configs", "fastsync-1k.json")["block_cap_txs"]
    assert (mix["driver"], mix["warm_blocks"]) == (sparse["driver"], sparse["warm_blocks"])
    assert mix["reads"]["kinds"] == sparse["reads"]["kinds"]
    cell, old = load("cells", CELL + ".json"), load("cells", "fastsync-1k.sparse.json")
    assert cell["metrics"] == ["catchup_blocks_per_s", "setup_s"]
    assert f"{cell['chain_blocks']:,}-block chain" in entry["why"] and f"{mix['reads']['per_s']} reads/s" in entry["why"]
    # what fastsync-1k.sparse reports, the stage clock with it, and the hash layer's readers; but not the
    # verify kernel's two: at under a block a second a window's launch runs at submit and is collected up
    # to 23 s later, so the 6 s stretch holds the record of one launch and the device time of another, or none
    assert cell["layer_metrics"] == [m for m in old["layer_metrics"] if m not in VERIFY_KERNEL] + HASH_READERS
    assert set(VERIFY_KERNEL) < set(old["layer_metrics"])
    per_layer = {m["name"]: m for m in b["per_layer"]}
    for name in cell["layer_metrics"]:
        assert CELL in per_layer[name]["workloads"]
    # the hash readers: every listed cell whose blocks are trees the device is due, this one among them
    from benchmark.lib.checks import DEVICE_MIN_LEAVES

    tree = listing.Listing()
    full_blocks = [c for c in tree.cells if tree.mix(c)["txs"]["per_block"] >= DEVICE_MIN_LEAVES]
    assert CELL in full_blocks
    for name in HASH_READERS:
        meta = load("layer_metrics", name + ".json")
        assert per_layer[name]["workloads"] == full_blocks and meta["what"]
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert meta[key] == per_layer[name][key]
    assert per_layer["kernel.merkle_tree_roofline"]["unit"] == "%"


# -- the readers ------------------------------------------------------------------------


def test_merkle_tree_bytes_against_hand_worked_bytes():
    from benchmark.lib import counts

    # one leaf: its block in, its digest out, no level
    assert counts.merkle_tree_bytes([15]) == 64 + 32
    # 0x00 + 54 bytes + 0x80 + the 8-byte length is one block; one byte more is two
    assert counts.merkle_tree_bytes([54]) == 64 + 32 and counts.merkle_tree_bytes([55]) == 128 + 32
    # three leaves: one pair at the first level (the third moves up free), one at the second
    assert counts.merkle_tree_bytes([15, 15, 15]) == 3 * 96 + 96 + 96
    # 10,000 one-block leaves: 9,999 pairs over the levels, as any binary tree has
    assert counts.merkle_tree_bytes([15] * 10_000) == 10_000 * 96 + 9_999 * 96 == 1_919_904


def tree(t, rows=10_000, backend="device", **more):
    return {"kind": "hash", "t": t, "backend": backend, "rows": rows, **more}


def test_the_tree_kernel_readers_over_a_hand_made_trace():
    from benchmark.lib import counts

    mix = {"txs": {"kind": "fixed_keys", "keys": 10_000, "per_block": 10_000}}
    obs = {
        "mix": mix, "device_kind": "TPU v5 lite", "heights": [48, 180],
        "trace": {"wall0": 1000.0, "window_s": 6.0, "chips": 1,
                  "modules": {"jit__leafhash_and_reduce(77)": 0.012, "jit_verify_tables_kernel(1)": 0.5},
                  "module_runs": {"jit__leafhash_and_reduce(77)": 24, "jit_verify_tables_kernel(1)": 2}},
        "launches": [tree(1001.0), tree(1002.0), tree(1003.0, rows=40, backend="host"),
                     {"kind": "tables", "t": 1002.5, "backend": "tables", "rows": 16_000}],
    }
    # 24 runs of 10,000 real leaves in 12 ms of device time
    assert reader("kernel.merkle_us_per_leaf")(obs) == pytest.approx(1e6 * 0.012 / 240_000)
    # block 49's txs are 15 bytes each (k0000000=490000): one SHA-256 block a leaf
    least = 24 * counts.merkle_tree_bytes([15] * 10_000) / 819e9
    assert reader("kernel.merkle_tree_roofline")(obs) == pytest.approx(100 * least / 0.012)
    assert reader("kernel.merkle_tree_roofline")(obs) < 100
    # no device tree in the window, no tree executable in the trace, or no trace: nothing to read
    for broken in (
        {**obs, "launches": obs["launches"][2:]},
        {**obs, "trace": {**obs["trace"], "modules": {"jit_verify_tables_kernel(1)": 0.5}}},
        {**obs, "trace": None},
        # trees of two sizes: a tree's leaves cannot be read off the records
        {**obs, "launches": [tree(1001.0), tree(1002.0, rows=9_000)]},
    ):
        assert reader("kernel.merkle_us_per_leaf")(broken) is None
        assert reader("kernel.merkle_tree_roofline")(broken) is None


def pull(text: str) -> dict:
    from benchmark.lib import rpc

    return rpc.parse_metrics(text)


def hash_pull(blocks, device_trees, host_small, host_big=0, fallback=0, failures=0, seconds=(0.0, 0.0)):
    rows = [f"tendermint_fastsync_blocks_applied_total {blocks}"]
    for backend, n_small, n_big, s in (("device", 0, device_trees, seconds[0]), ("host", host_small, host_big, seconds[1])):
        rows += [
            f'tendermint_hash_batch_leaves_bucket{{backend="{backend}",le="4096"}} {n_small}',
            f'tendermint_hash_batch_leaves_bucket{{backend="{backend}",le="8192"}} {n_small}',
            f'tendermint_hash_batch_leaves_bucket{{backend="{backend}",le="16384"}} {n_small + n_big}',
            f'tendermint_hash_batch_leaves_bucket{{backend="{backend}",le="+Inf"}} {n_small + n_big}',
            f'tendermint_hash_batch_leaves_count{{backend="{backend}"}} {n_small + n_big}',
            f'tendermint_hash_seconds_sum{{backend="{backend}"}} {s!r}',
        ]
    rows += [
        f'tendermint_device_fallback_calls_total{{kind="hash"}} {fallback}',
        f'tendermint_device_dispatch_failures_total{{kind="hash"}} {failures}',
        'tendermint_device_fallback_calls_total{kind="verify"} 5',
    ]
    return pull("\n".join(rows) + "\n")


def test_a_device_sized_tree_on_the_host_is_counted_however_it_shows():
    from benchmark.lib import checks

    sound = hash_pull(178, device_trees=178, host_small=900)
    assert checks.hash_host_fallbacks([tree(1.0)] * 178, sound) == 0
    # small trees are the host's to answer: 8,191 leaves and a commit's thousand
    assert checks.hash_host_fallbacks([tree(1.0, rows=8_191, backend="host"), tree(1.0, rows=1_000, backend="host")], sound) == 0
    # outside a launch context a host tree closes no record: the histogram shows it
    assert checks.hash_host_fallbacks([], hash_pull(178, device_trees=0, host_small=900, host_big=178)) == 178
    # inside one its record does, as a failed device tree's does
    assert checks.hash_host_fallbacks([tree(1.0, backend="host"), tree(1.0, error="Boom")], sound) == 2
    # the spine's own counters, under the hasher's kind alone
    assert checks.hash_host_fallbacks([], hash_pull(178, 176, 900, fallback=2, failures=3)) == 5
    assert reader("hash.host_fallbacks")({"hash_host_fallbacks": 3}) == 3
    assert reader("hash.host_fallbacks")({}) is None


def test_tree_ms_per_block_is_every_backends_seconds_over_the_blocks_applied():
    start = hash_pull(48, 48, 300, seconds=(1.0, 0.25))
    end = hash_pull(178, 178, 1_100, seconds=(1.0 + 0.65, 0.25 + 0.13))
    obs = {"metrics_start": start, "metrics_end": end}
    assert reader("hash.tree_ms_per_block")(obs) == pytest.approx(1e3 * 0.78 / 130)
    assert reader("hash.tree_ms_per_block")({"metrics_start": start, "metrics_end": start}) is None
    older = pull("tendermint_fastsync_blocks_applied_total 48\n")
    assert reader("hash.tree_ms_per_block")({"metrics_start": older, "metrics_end": older}) is None


def test_trace_reduce_counts_an_executables_runs():
    from benchmark.lib import trace_reduce

    with open(os.path.join(BENCH, "lib", "recorded_trace.json")) as f:
        rec = json.load(f)
    red = trace_reduce.reduce(rec["trace"], rec["window_s"])
    assert set(red["module_runs"]) == set(red["modules"])
    events = sum(
        len(ln["events"]) for p in rec["trace"]["planes"] for ln in p["lines"] if ln["name"] == trace_reduce.MODULES_LINE
    )
    assert sum(red["module_runs"].values()) * red["chips"] == events > 0


# -- the cell, from its own files, at a scale the CPU can hold ---------------------------


def copy_with(tmp_path, config: dict, mix: dict, cell: dict):
    """A temp copy of the benchmark whose `fastsync-1k`, `full` and
    `fastsync-1k.full` files are updated with these keys."""
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.ignore_patterns("out", "cache", "__pycache__"))
    os.symlink(os.path.join(ROOT, "tendermint_tpu"), tmp_path / "tendermint_tpu")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    for parts, change in (
        (("configs", "fastsync-1k.json"), config), (("traffic", "full.json"), mix), (("cells", CELL + ".json"), cell),
    ):
        doc = load(*parts)
        for key, value in change.items():
            if isinstance(value, dict):
                doc[key] = {**doc[key], **value}
            else:
                doc[key] = value
        with open(os.path.join(tmp_path, "benchmark", *parts), "w") as f:
            json.dump(doc, f)
    return tmp_path


def run_cell(top, seed, *more, seconds="3", trace="1", env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", str(seed),
         "--seconds", seconds, "--trace", trace, "--allow-cpu-for-tests", *more],
        cwd=top, env=env, capture_output=True, text=True, timeout=600,
    )


def wrong_rows(proc) -> list[str]:
    # (every row: the last-write check, which raced the apply in about one tiny
    # CPU run in ten, asks at the app's own height since PR 42)
    return [row for row in proc.stdout.splitlines() if "NOT CORRECT" in row]


def test_the_cell_rehearsed_from_its_own_files_with_its_scale_cut(tmp_path):
    """`fastsync-1k.full` as the driver runs it, traced, but for
    `validators` (13: under the 512 lanes from which a launch is the
    device's, which the CPU cannot answer) and `keys` and `per_block` (200
    over 200: under the 8,192 leaves from which a tree is the device's);
    blocks that light go by at over a hundred a second, so the chain is
    longer too."""
    top = copy_with(tmp_path, {"validators": 13}, {"txs": {"keys": 200, "per_block": 200}}, {"chain_blocks": 1500})
    proc = run_cell(top, 3500000011)
    assert proc.returncode in (0, 1), proc.stdout[-3000:] + proc.stderr[-3000:]
    assert not wrong_rows(proc), wrong_rows(proc)
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    got = line["metrics"]
    assert got["verify.host_fallbacks"]["value"] == 0.0 and got["hash.host_fallbacks"]["value"] == 0.0
    assert line["compared"]["tree_host_answers"] == [0, 0]
    assert got["hash.tree_ms_per_block"]["value"] > 0 and got["hash.tree_ms_per_block"]["unit"] == "ms"
    assert got["hash.device_leaf_share"]["value"] == 0.0  # every tree is under 8,192 leaves here
    for stage in ("decode", "part_set", "store", "validate", "exec", "state_save"):
        assert got[f"fastsync.{stage}_ms_per_block"]["value"] > 0
    # no device answered on the CPU: the trace readers find nothing
    assert not {"device.idle_share", "kernel.verify_us_per_sig", "kernel.merkle_us_per_leaf", "kernel.merkle_tree_roofline"} & set(got)
    detail = json.load(open(top / "benchmark" / "out" / f"{CELL}-3500000011.json"))
    assert detail["notes"]["heights"][0] >= 48  # the mix's warm_blocks, untouched


@pytest.fixture(scope="module")
def device_sized(tmp_path_factory):
    """The cell with trees the device is due: 8,200 txs a block over 8,200
    keys, 4 validators, and a chain and a warm-up short enough for the CPU."""
    return copy_with(
        tmp_path_factory.mktemp("full_copy"), {"validators": 4},
        {"txs": {"keys": 8_200, "per_block": 8_200}, "warm_blocks": 6}, {"chain_blocks": 120},
    )


# `auto_hasher()` gives the CPU the host's tree hasher; with the fault
# injector armed (for a kind nothing dispatches) it gives the device's,
# behind the breaker, as on a TPU: the tree then runs as XLA's CPU program
DEVICE_TREES_ON_CPU = {"TENDERMINT_TPU_DEVICE_FAIL": "none:0"}


def test_device_sized_trees_answered_by_the_device_tree_are_correct(device_sized):
    proc = run_cell(device_sized, 3500000029, env=DEVICE_TREES_ON_CPU)
    assert proc.returncode in (0, 1), proc.stdout[-3000:] + proc.stderr[-3000:]
    assert not wrong_rows(proc), wrong_rows(proc)
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["metrics"]["hash.host_fallbacks"]["value"] == 0.0
    assert line["metrics"]["hash.device_leaf_share"]["value"] > 90


@pytest.mark.parametrize("how", ["control", "cpu_default"])
def test_host_trees_turns_correct_false(device_sized, how):
    """The control `host_trees` builds the node with the host's tree
    hasher; and without the control or the armed injector the CPU's node
    has that hasher anyway. Either way a tree of 8,200 leaves is answered
    by the host where the device is due, and `correct` is false."""
    if how == "control":
        proc = run_cell(device_sized, 3500000031, "--control", "host_trees", trace="0", env=DEVICE_TREES_ON_CPU)
    else:
        proc = run_cell(device_sized, 3500000031, trace="1")
    assert proc.returncode == 1, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] > 0
    assert line["compared"]["tree_host_answers"][0] > 0 and line["compared"]["tree_host_answers"][1] == 0
    assert "were not answered by the device" in proc.stdout
    assert wrong_rows(proc) and all("Merkle trees" in row for row in wrong_rows(proc))
    if how == "cpu_default":
        assert line["metrics"]["hash.host_fallbacks"]["value"] > 0
