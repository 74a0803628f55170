"""The validator-set rule as a file, the app from the deployment and the
per-height reference (PR 36), on the CPU, with the deployment
`valchange-1k`, the mix `rotate` and the cell `valchange-1k.rotate` as
files of these tests (`withheld/`): the benchmark does not list them,
because the program's incremental table build gives a joining validator a
pad column's table at the TPU's launch shape (the last test here; PERF.md
section 7's first entry):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

What the rule writes, that the chain the generator makes from it is the
one `reference.py` derives (every header's `validators_hash`, every
commit against the set of its height), that chains of mixes without
`valset` are the chains they were, the arithmetic of the three new
readers over hand-made pulls, and rehearsals of the cell on a
16-validator scratch deployment the test writes. Nothing here yields a
device number.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

CELL = "valchange-1k.rotate"
READERS = ["fastsync.boundary_window_share", "verify.table_cache_miss_share", "verify.table_incremental_share"]
EVENTS = "tendermint_verify_table_cache_total"


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def withheld(name):
    """The deployment's, the mix's and the cell's file, kept beside these
    tests until the program can sync the cell on the chip: the PR that
    repairs it copies them to `configs/`, `traffic/` and `cells/`."""
    return load("tests", "withheld", name)


def reader(name):
    spec = importlib.util.spec_from_file_location("m", os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce


def scratch_config(n_vals=16) -> dict:
    cfg = withheld("valchange-1k.json")
    cfg.update(name=f"scratch{n_vals}", validators=n_vals)
    return cfg


# -- the rule -------------------------------------------------------------------------


def rule_changes(n_vals, n_blocks, every=4):
    from benchmark.lib import chain

    mix = {"valset": {"kind": "reactor_cycle", "every": every}}
    return chain.valset_changes(scratch_config(n_vals), mix, n_blocks)


def test_reactor_cycle_is_the_tests_four_steps_and_ours_every_four_heights():
    got = rule_changes(16, 120)
    # cycle 0: a joins at 10, goes to 25, b and c join, b and c leave, a leaves
    assert [got[h] for h in (4, 8, 12, 16, 20)] == [
        [(16, 10)], [(16, 25)], [(17, 10), (18, 10)], [(17, 0), (18, 0)], [(16, 0)],
    ]
    # cycle 1 takes three new keys: a validator that left does not come back
    assert [got[h] for h in (24, 28, 32, 36, 40)] == [
        [(19, 10)], [(19, 25)], [(20, 10), (21, 10)], [(20, 0), (21, 0)], [(19, 0)],
    ]
    assert sorted(got) == list(range(4, 81, 4))  # four whole cycles, none open at the tail
    # a mix without `valset` changes nothing; an unknown rule names its file
    from benchmark.lib import chain

    assert chain.valset_changes(scratch_config(), load("traffic", "sparse.json"), 120) == {}
    with pytest.raises(ValueError) as e:
        chain.valset_changes(scratch_config(), {"valset": {"kind": "no_such_rule"}}, 120)
    assert os.path.join(BENCH, "valset_rules", "no_such_rule.py") in str(e.value)


@pytest.mark.parametrize("n_blocks", [54, 55, 95, 120, 400, 800])
def test_no_cycle_is_open_in_the_chains_last_34_heights(n_blocks):
    got = rule_changes(1000, n_blocks)
    assert len(got) % 5 == 0  # whole cycles only
    # the last step's change takes effect one height on, before the tail starts
    assert max(got, default=0) + 1 <= n_blocks - 34 + 1
    assert len(got) // 5 == (n_blocks - 34) // 20
    # the set stays inside 1,000 to 1,003 keys: 24 pad columns hold them
    size, top = 1000, 1000
    for h in sorted(got):
        size += sum(1 if w == 10 else -1 if w == 0 else 0 for _r, w in got[h])
        top = max(top, size)
    assert size == 1000 and top == (1003 if got else 1000)


# -- the chain against the per-height reference ----------------------------------------


@pytest.fixture(scope="module")
def rotating(tmp_path_factory):
    """A 120-block chain of the 16-validator scratch deployment under the
    mix `rotate`, with its blocks decoded and the reference's sets."""
    from benchmark.lib import chain, checks
    from tendermint_tpu.types.block import Block

    home = str(tmp_path_factory.mktemp("rotating"))
    cfg, mix = scratch_config(), withheld("rotate.json")
    rec = chain.build_chain(cfg, mix, seed=3600000001, n_blocks=120, home=home, workers=0)
    blocks = [Block.decode(b) for b in chain.read_blocks(home)]
    return rec, blocks, checks.reference_sets(rec, cfg, mix), cfg, mix


def served(commit) -> dict:
    """A commit as the `/commit` route serves it (`rpc/core.py`)."""

    def block_id(b):
        return {"hash": b.hash.hex(), "parts": {"total": b.parts_header.total, "hash": b.parts_header.hash.hex()}}

    return {
        "block_id": block_id(commit.block_id),
        "precommits": [
            None if v is None else {
                "validator_index": v.validator_index, "height": v.height, "round": v.round,
                "timestamp": v.timestamp, "type": v.type, "block_id": block_id(v.block_id),
                "signature": v.signature.hex(),
            }
            for v in commit.precommits
        ],
    }


def test_every_header_carries_the_references_validators_hash(rotating):
    from benchmark.lib import checks, reference

    rec, blocks, sets, _cfg, _mix = rotating
    assert len(sets) == 21 and [s["from_height"] for s in sets] == [1, *range(5, 82, 4)]
    assert [len(s["pubkeys"]) for s in sets[:6]] == [16, 17, 17, 19, 17, 16]
    assert [sum(s["powers"]) for s in sets[:6]] == [160, 170, 185, 205, 185, 160]
    for blk in blocks:
        h = blk.header.height
        assert blk.header.validators_hash == reference.set_at(sets, h)["validators_hash"], h
        assert rec.set_at(h)["validators_hash"] == blk.header.validators_hash.hex()
    # the generator's record of the sets is the reference's, stretch for stretch
    assert checks.record_sets_differ(rec, sets) == 0
    assert checks.record_sets_differ(rec, sets[:1]) == 20
    # the tail is the genesis set's: warm shapes and the planted fault take it
    assert rec.set_at(120 - 33)["validators_hash"] == rec.validators_hash == sets[0]["validators_hash"].hex()
    assert len(checks.validator_set(rec)) == 16
    # the four heights around the first and the last change applied by height 60
    assert checks.boundary_heights(sets, 60) == [4, 5, 6, 7, 56, 57, 58, 59]
    assert checks.boundary_heights(sets, 5) == [4, 5] and checks.boundary_heights(sets[:1], 60) == []


def test_every_commit_verifies_against_the_set_of_its_height_and_not_its_neighbours(rotating):
    from benchmark.lib import reference

    rec, blocks, sets, _cfg, _mix = rotating
    crossed = 0
    for blk, nxt in zip(blocks, blocks[1:]):
        h = blk.header.height
        com = served(nxt.last_commit)
        here = reference.set_at(sets, h)
        assert reference.check_commit(rec.chain_id, h, rec.block_hash[h - 1], com, here["pubkeys"], here["powers"]) == []
        for other in (reference.set_at(sets, h - 1), reference.set_at(sets, h + 1)):
            if other["pubkeys"] != here["pubkeys"]:  # a membership step, not a change of power
                crossed += 1
                assert reference.check_commit(
                    rec.chain_id, h, rec.block_hash[h - 1], com, other["pubkeys"], other["powers"]
                )
    assert crossed == 2 * 4 * 4  # four membership steps a cycle, seen from both sides


def test_val_txs_go_in_front_and_are_never_a_blocks_last_tx(rotating):
    from benchmark.lib import chain

    rec, blocks, _sets, cfg, mix = rotating
    changes = chain.valset_changes(cfg, mix, 120)
    for blk in blocks:
        txs = [bytes(t) for t in blk.data.txs]
        n_val = len(changes.get(blk.header.height, ()))
        assert [t.startswith(b"val:") for t in txs] == [True] * n_val + [False] * 3
        assert txs[n_val:] == chain.block_txs(mix, blk.header.height)
        key, _, value = txs[-1].partition(b"=")
        assert rec.last_write[blk.header.height - 1] == [key.hex(), value.hex()]
    a = bytes.fromhex(next(k for k in rec.valsets[1]["pubkeys"] if k not in rec.pubkeys))
    assert [bytes(t) for t in blocks[3].data.txs][0] == b"val:%s/10" % a.hex().encode()
    assert [bytes(t) for t in blocks[7].data.txs][0] == b"val:%s/25" % a.hex().encode()


def test_the_reference_derives_a_set_itself():
    from benchmark.lib import reference

    address_of = {bytes([i]) * 32: bytes([9 - i]) * 20 for i in range(1, 5)}
    k1, k2, k3, k4 = sorted(address_of)
    sets = reference.validator_sets(
        [(k1, 10), (k2, 20)], {4: [(k3, 5)], 8: [(k1, 0), (k2, 21)], 9: [(k4, 1)]}, address_of,
    )
    # ordered by address (k4 first: its address is the lowest), effective one height on
    assert [(s["from_height"], s["pubkeys"], s["powers"]) for s in sets] == [
        (1, [k2, k1], [20, 10]), (5, [k3, k2, k1], [5, 20, 10]), (9, [k3, k2], [5, 21]), (10, [k4, k3, k2], [1, 5, 21]),
    ]
    assert [reference.set_at(sets, h)["from_height"] for h in (1, 4, 5, 8, 9, 10, 99)] == [1, 1, 5, 5, 9, 10, 10]
    # the root, with the leaf's bytes written out: two leaves under one inner node
    leaf = lambda a, k, w: hashlib.sha256(b"\x00\x14" + a + b"\x20" + k + bytes([w])).digest()  # noqa: E731
    want = hashlib.sha256(b"\x01" + leaf(address_of[k2], k2, 20) + leaf(address_of[k1], k1, 10)).digest()
    assert sets[0]["validators_hash"] == want
    assert reference.uvarint(300) == b"\xac\x02" and reference.uvarint(127) == b"\x7f"
    # and the program's own root of the same set is that root
    from tendermint_tpu.crypto.keys import PubKey
    from tendermint_tpu.types import Validator, ValidatorSet

    pubs = [PubKey(bytes([i]) * 32) for i in (1, 2, 3)]
    rows = sorted((p.address, p.data, 7 + i) for i, p in enumerate(pubs))
    program = ValidatorSet([Validator(address=a, pub_key=PubKey(k), voting_power=w) for a, k, w in rows])
    assert reference.validators_hash(rows) == program.hash()
    with pytest.raises(ValueError):
        reference.validator_sets([(k1, 10)], {3: [(k2, 0)]}, address_of)


# -- what did not change -----------------------------------------------------------------


@pytest.mark.parametrize(
    "mix, cut, blocks, digest, blob",
    [
        ("sparse", {}, 24, "dfce3a2f2465dc11", "cb95c2379d999f29"),
        ("full", {"keys": 50, "per_block": 50}, 12, "8fcf93697b707c06", "8d24a7357a1f7f76"),
    ],
)
def test_a_chain_of_a_mix_without_valset_is_byte_for_byte_the_chain_it_was(tmp_path, mix, cut, blocks, digest, blob):
    """The digests and the hash of `blocks.bin` as the tree before PR 36
    built them (commit d5c90b3): the accepted cells sync the same bytes."""
    from benchmark.lib import chain

    config = load("configs", "fastsync-100.json")
    config["validators"] = 7
    doc = load("traffic", mix + ".json")
    doc["txs"].update(cut)
    rec = chain.build_chain(config, doc, seed=2147483659, n_blocks=blocks, home=str(tmp_path), workers=0)
    assert chain.digest(rec) == digest
    with open(tmp_path / "blocks.bin", "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest()[:16] == blob
    # one stretch, the genesis set's; and a record from before `valsets` loads and means the same
    assert [s["from_height"] for s in rec.valsets] == [1] and rec.valsets[0]["pubkeys"] == rec.pubkeys
    old = {k: v for k, v in rec.__dict__.items() if k not in ("valsets", "addresses")}
    with open(tmp_path / "old.json", "w") as f:
        json.dump(old, f)
    loaded = chain.Record.load(str(tmp_path / "old.json"))
    assert loaded.valsets == [] and loaded.set_at(9)["validators_hash"] == rec.validators_hash
    from benchmark.lib import checks

    sets = checks.reference_sets(loaded, config, doc)
    assert len(sets) == 1 and sets[0]["pubkeys"] == [bytes.fromhex(k) for k in rec.pubkeys]
    assert checks.record_sets_differ(loaded, sets) == 0
    assert sets[0]["validators_hash"].hex() == checks.reference_sets(rec, config, doc)[0]["validators_hash"].hex() == rec.validators_hash


def test_the_app_comes_from_the_deployments_file(tmp_path):
    from benchmark.lib import chain
    from tendermint_tpu.abci.apps import KVStoreApp, PersistentKVStoreApp

    assert type(chain.make_app("kvstore")) is KVStoreApp
    assert type(chain.make_app("persistent_kvstore")) is PersistentKVStoreApp
    app = chain.make_app("persistent_kvstore", str(tmp_path / "data" / "app.db"))
    app.deliver_tx(b"k=v")
    app.end_block(1)
    app.commit()
    assert chain.make_app("persistent_kvstore", str(tmp_path / "data" / "app.db")).query("", b"k").value == b"v"
    with pytest.raises(ValueError, match="unknown app 'counter'"):
        chain.make_app("counter")
    # every deployment's app is one the generator knows
    for name in os.listdir(os.path.join(BENCH, "configs")):
        chain.make_app(load("configs", name)["app"])
    # a mix that changes the set over an app that never does is refused, not built
    cfg = scratch_config()
    cfg["app"] = "kvstore"
    with pytest.raises(ValueError, match="needs a deployment whose app does"):
        chain.build_chain(cfg, withheld("rotate.json"), seed=1, n_blocks=60, home=str(tmp_path / "c"), workers=0)


# -- the files -----------------------------------------------------------------------------


def test_the_deployment_is_fastsync_1k_with_the_app_that_changes_the_set():
    new, old = withheld("valchange-1k.json"), load("configs", "fastsync-1k.json")
    assert set(new) - set(old) == {"standby_power", "updated_power"} and set(old) <= set(new)
    assert {k for k in old if new[k] != old[k]} == {"name", "source", "deployment", "app", "guarantees", "assumed"}
    assert (new["app"], new["standby_power"], new["updated_power"]) == ("persistent_kvstore", 10, 25)
    assert new["validators"] == 1000 and new["power"] == old["power"] and new["absent_votes"] == 0
    assert new["guarantees"][:3] == old["guarantees"] and "at that height" in new["guarantees"][3]
    assert new["reduced"] == ["source_blocks"] and new["source_blocks"] == 50_000
    assert "no validator-set change" not in new["assumed"]["rotation"]
    assert "TestReactorValidatorSetChanges" in new["source"] and "persistent_dummy" in new["source"]
    assert len(new["source"]) <= 200 and new["source"] != old["source"]
    mix = withheld("rotate.json")
    assert mix["valset"] == {"kind": "reactor_cycle", "every": 4} and mix["warm_blocks"] == 48
    assert mix["txs"] == load("traffic", "sparse.json")["txs"] and mix["reads"] == load("traffic", "sparse.json")["reads"]
    assert set(mix["assumed"]) >= {"quoted_from_memory", "fifth_step", "fresh_keys_each_cycle", "cadence"}


def test_the_cell_lists_what_fastsync_1k_sparse_lists_and_the_three_readers():
    cell, old = withheld(CELL + ".json"), load("cells", "fastsync-1k.sparse.json")
    assert cell["chain_blocks"] == 800 and cell["trace_seconds"] == 6
    assert cell["metrics"] == ["catchup_blocks_per_s", "setup_s"]
    # (what `fastsync-1k.sparse` listed when PR 36 wrote the file: PR 42 listed PR 38's readers after them)
    later = [*withheld("own_work.json")["layer_metrics"], "entry.block_answer_bytes"]
    assert cell["layer_metrics"] == [n for n in old["layer_metrics"] if n not in later] + READERS
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    per_layer = {m["name"]: m for m in b["per_layer"]}
    for name in READERS:
        meta = load("layer_metrics", name + ".json")
        assert meta["name"] == name and meta["what"] and meta["moves"] == "catchup_blocks_per_s"
        # no cell lists them while the cell is withheld; the PR that adds it lists them as their files say
        for key in ("unit", "better", "source", "layer", "moves"):
            assert per_layer.get(name, meta)[key] == meta[key]


# -- the readers ------------------------------------------------------------------------------


def pull(**series):
    from benchmark.lib import rpc

    rows = []
    for name, value in series.items():
        base, _, label = name.partition("__")
        rows.append(f"{base}{{{label.replace('_', '=\"', 1)}\"}} {value!r}\n" if label else f"{base} {value!r}\n")
    return rpc.parse_metrics("".join(rows))


WINDOWS = "tendermint_fastsync_windows_total"


def test_the_three_readers_over_a_hand_made_pair_of_pulls():
    start = pull(tendermint_fastsync_blocks_applied_total=48, **{
        WINDOWS + "__cut_boundary": 20, WINDOWS + "__cut_pool_gap": 4, WINDOWS + "__cut_full": 0,
        EVENTS + "__event_hit": 40, EVENTS + "__event_miss": 6, EVENTS + "__event_incremental": 5,
    })
    end = pull(tendermint_fastsync_blocks_applied_total=348, **{
        WINDOWS + "__cut_boundary": 170, WINDOWS + "__cut_pool_gap": 10, WINDOWS + "__cut_full": 4,
        EVENTS + "__event_hit": 190, EVENTS + "__event_miss": 36, EVENTS + "__event_incremental": 32,
    })
    obs = {"metrics_start": start, "metrics_end": end, "window": [1000.0, 1030.0]}
    assert reader("fastsync.boundary_window_share")(obs) == pytest.approx(100 * 150 / 160)
    assert reader("fastsync.full_window_share")(obs) == pytest.approx(100 * 4 / 160)
    assert reader("verify.table_cache_miss_share")(obs) == pytest.approx(100 * 30 / 180)
    assert reader("verify.table_incremental_share")(obs) == pytest.approx(100 * 27 / 30)
    # a static set: no boundary and no miss inside the window
    still = {"metrics_start": start, "window": [0.0, 30.0], "metrics_end": pull(
        tendermint_fastsync_blocks_applied_total=348, **{
            WINDOWS + "__cut_boundary": 20, WINDOWS + "__cut_pool_gap": 30,
            EVENTS + "__event_hit": 90, EVENTS + "__event_miss": 6, EVENTS + "__event_incremental": 5,
        })}
    assert reader("fastsync.boundary_window_share")(still) == 0.0
    assert reader("verify.table_cache_miss_share")(still) == 0.0
    assert reader("verify.table_incremental_share")(still) is None  # nothing was built: no share of nothing


@pytest.mark.parametrize("name", READERS)
def test_a_new_reader_gives_none_where_there_is_nothing_to_read(name):
    # a program without the series, and a window with no block applied or no lookup
    older = {"metrics_start": pull(tendermint_fastsync_blocks_applied_total=48),
             "metrics_end": pull(tendermint_fastsync_blocks_applied_total=408), "window": [0.0, 30.0]}
    assert reader(name)(older) is None
    same = pull(tendermint_fastsync_blocks_applied_total=48, **{
        WINDOWS + "__cut_boundary": 20, EVENTS + "__event_hit": 40, EVENTS + "__event_miss": 6})
    assert reader(name)({"metrics_start": same, "metrics_end": same, "window": [0.0, 30.0]}) is None


# -- the cell, on the scratch deployment ------------------------------------------------------


@pytest.fixture(scope="module")
def scratch(tmp_path_factory):
    """A temp copy of the benchmark with the 16-validator scratch
    deployment dropped in as new files and entries under the mix `rotate`,
    and one of 13 validators under the mix `sparse` (two coalesced windows
    of 16 commits of 13 stay under the 512 lanes from which a launch is
    the device's to answer; a rotating set's windows carry three)."""
    top = tmp_path_factory.mktemp("valchange_copy")
    shutil.copytree(BENCH, top / "benchmark", ignore=shutil.ignore_patterns("out", "cache", "__pycache__"))
    os.symlink(os.path.join(ROOT, "tendermint_tpu"), top / "tendermint_tpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = withheld(CELL + ".json")
    cell["chain_blocks"] = 1500  # blocks this light go by at a hundred a second
    shutil.copy(os.path.join(HERE, "withheld", "rotate.json"), top / "benchmark" / "traffic" / "rotate.json")
    for n_vals, mix in ((16, "rotate"), (13, "sparse")):
        name = f"scratch{n_vals}"
        with open(top / "benchmark" / "configs" / f"{name}.json", "w") as f:
            json.dump(scratch_config(n_vals), f)
        b["configs"].append({"name": name, "source": "test", "file": f"benchmark/configs/{name}.json", "reduced": [], "why": "test"})
        b["workloads"].append({"name": f"{name}.{mix}", "config": name, "traffic": mix, "chips": 1, "why": "test"})
        with open(top / "benchmark" / "cells" / f"{name}.{mix}.json", "w") as f:
            json.dump(cell, f)
    with open(top / "BENCHMARK.json", "w") as f:
        json.dump(b, f)
    return top


def run_cell(top, cell, seed, *more, trace="0"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", str(seed),
         "--seconds", "3", "--trace", trace, "--allow-cpu-for-tests", *more],
        cwd=top, env=env, capture_output=True, text=True, timeout=600,
    )


def wrong_rows(text) -> list[str]:
    # (every row: the last-write check, which raced the apply in about one tiny
    # CPU run in ten, asks at the app's own height since PR 42)
    return [row for row in text.splitlines() if "NOT CORRECT" in row]


def test_the_cell_rehearsed_with_the_table_cache_on_the_cpu(scratch, monkeypatch, capfd):
    """The whole run in this process, the look for a chip skipped, with the
    verifier a TPU process has (`TableBatchVerifier` behind the resilient
    and the coalescing layers) in place of the CPU's host verifier: every
    window here is under 512 lanes and goes to the host library, and
    `prebuild` builds each new set's table, the first from nothing (on the
    host: XLA's CPU would compile the build kernel for minutes), the later
    ones by concatenation and gather from a cached set."""
    import jax  # noqa: F401 - the driver needs a backend, here the CPU's

    from tendermint_tpu.ops import ed25519_tables
    from tendermint_tpu.services import verifier as verifier_mod
    from tendermint_tpu.services.batcher import CoalescingVerifier
    from tendermint_tpu.services.resilient import ResilientVerifier

    host_build = ed25519_tables.host_build_key_tables
    monkeypatch.setattr(ed25519_tables, "build_key_tables", lambda keys: host_build([bytes(k) for k in keys]))
    monkeypatch.setattr(
        verifier_mod, "_DEFAULT", CoalescingVerifier(ResilientVerifier(verifier_mod.TableBatchVerifier()))
    )
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(scratch)
    spec = importlib.util.spec_from_file_location("bench_run_copy", scratch / "benchmark" / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    monkeypatch.setattr(sys, "path", list(sys.path))
    code = run.main(["--workload", "scratch16.rotate", "--seed", "3600000013", "--seconds", "3",
                     "--trace", "1", "--allow-cpu-for-tests"])
    out, err = capfd.readouterr()
    assert code in (0, 1), out[-3000:] + err[-3000:]
    assert not wrong_rows(out), wrong_rows(out)
    line = json.loads(out.strip().splitlines()[-1])
    got = line["metrics"]
    assert list(line)[-2:] == ["table_cache_events", "compared"]
    events = line["table_cache_events"]
    # (a build in flight when the window opens counted its miss before it: miss + 1 >= incremental)
    assert events["incremental"] >= 1 and events["host_build"] == 0 and events["miss"] + 1 >= events["incremental"]
    assert "table_cache_events: " in err and err.strip().splitlines()[-1].startswith("compared ")
    assert got["fastsync.boundary_window_share"]["value"] > 50 and got["fastsync.full_window_share"]["value"] == 0.0
    assert got["verify.table_cache_miss_share"]["value"] > 0
    assert got["verify.table_incremental_share"]["value"] > 0
    assert 0.2 <= got["fastsync.valset_roots_per_block"]["value"] <= 0.55  # one root a change, one change in four heights
    # (`fastsync.compiles_in_window` is not held to 0 here: at a hundred blocks a second a set's
    # build is still in flight when the next is asked for, so the next concatenates to an older
    # set, and without the TPU's padding to 128 columns every such pair of sizes is a new shape)
    assert got["verify.host_fallbacks"]["value"] == 0.0
    for name in ("record_valsets_differ", "sample_differ", "sample_valset_hash_differ", "sample_commit_differ"):
        assert line["compared"][name] == [0, 0]
    sampled = next(row for row in out.splitlines() if "check sample:" in row)
    # (8, or fewer where the window closed less than two heights past the last change applied)
    assert re.search(r"[5-8] of them around the first and the last set change applied: \[4, 5, 6, 7,", sampled), sampled
    detail = json.load(open(scratch / "benchmark" / "out" / "scratch16.rotate-3600000013.json"))
    assert detail["notes"]["heights"][0] >= 48 and detail["notes"]["table_cache_events"] == events


@pytest.mark.parametrize(
    "cell, control, correct",
    [
        ("scratch16.rotate", "", True),
        ("scratch16.rotate", "static_reference", False),
        ("scratch13.sparse", "static_reference", True),
        ("scratch16.rotate", "accept_all", False),
        ("scratch16.rotate", "apphash_off_by_one", False),
    ],
)
def test_correct_under_each_control_on_the_scratch_cells(scratch, cell, control, correct):
    """`static_reference` holds the sample to the genesis set at every
    height: false where the chain's set changes (it shows that the chain
    rotates and that the comparison would notice a node that did not),
    true where it does not; the older controls break the new cell as they
    break the others."""
    proc = run_cell(scratch, cell, 3600000017, *(["--control", control] if control else []))
    assert proc.returncode in (0, 1), proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    if correct:
        assert not wrong_rows(proc.stdout), wrong_rows(proc.stdout)
        assert line["compared"]["sample_valset_hash_differ"] == [0, 0]
        assert ("table_cache_events" in line) == cell.endswith(".rotate")
        return
    assert proc.returncode == 1 and line["correct"] is False and line["failed"] > 0
    assert wrong_rows(proc.stdout)
    if control == "static_reference":
        compared = line["compared"]
        assert compared["sample_valset_hash_differ"][0] > 0 and compared["sample_commit_differ"][0] > 0
        assert compared["record_valsets_differ"] == [0, 0]  # the chain is sound: the reference held to it was not
        assert any("validators_hash" in row for row in wrong_rows(proc.stdout))


# -- why the cell is withheld: the program's fault, on the CPU ---------------------------------------


def joined_lanes_refused(fused: bool) -> list[list[int]]:
    """Sixteen validators, then one more, then two more, each set's table
    asked for as a launch asks (`TableBatchVerifier.verify_commits`, every
    lane a sound signature of its key): the lanes refused at each of the
    three sets. `fused` is the launch shape: the TPU pads a set to 128
    columns with `PLACEHOLDER_KEY`, the CPU does not."""
    import numpy as np

    from benchmark.lib import signer
    from tendermint_tpu.services import verifier as verifier_mod

    keys = [signer.private_key(7, i) for i in range(19)]
    pubs = [signer.public_bytes(k) for k in keys]
    verifier = verifier_mod.TableBatchVerifier(min_device_batch=0)
    refused = []
    g = list(range(16))
    for ranks in (g, g[:5] + [16] + g[5:], g[:3] + [17] + g[3:5] + [16] + g[5:] + [18]):
        msg = b"height of %d validators" % len(ranks)
        commit = ([msg] * len(ranks), [keys[r].sign(msg) for r in ranks])
        verdicts = verifier.verify_commits([pubs[r] for r in ranks], [commit], force_fused=fused)
        refused.append(np.where(~np.asarray(verdicts)[0])[0].tolist())
    return refused


def test_without_the_tpus_padding_a_joined_validators_signature_verifies(monkeypatch):
    """The second witness: the same program on the CPU's launch shape
    answers as the reference (the host library) does."""
    from tendermint_tpu.ops import ed25519_tables

    host_build = ed25519_tables.host_build_key_tables
    monkeypatch.setattr(ed25519_tables, "build_key_tables", lambda keys: host_build([bytes(k) for k in keys]))
    assert joined_lanes_refused(fused=False) == [[], [], []]


@pytest.mark.xfail(
    strict=False,
    reason="tendermint_tpu/services/verifier.py `_incremental_build`: `n_old = len(pos)` counts the cached set's "
    "DISTINCT keys, and a set padded to its launch width repeats PLACEHOLDER_KEY, so a new key's column index falls "
    "among the cached pad columns: the validator that joined is refused, fast-sync drops its only peer (PERF.md section 7)",
)
def test_at_the_tpus_launch_shape_a_joined_validators_signature_verifies(monkeypatch):
    """What `valchange-1k.rotate` needs of the program and does not get:
    at the padded shape the lanes of the validators that joined (lane 5,
    then lanes 3, 6 and 18) read False though their signatures are
    sound. Passes once the program is repaired."""
    from tendermint_tpu.ops import ed25519_tables

    host_build = ed25519_tables.host_build_key_tables
    monkeypatch.setattr(ed25519_tables, "build_key_tables", lambda keys: host_build([bytes(k) for k in keys]))
    assert joined_lanes_refused(fused=True) == [[], [], []]
