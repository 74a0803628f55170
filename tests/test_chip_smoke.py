"""chip_smoke.py without a chip: its chain builder and its pass/fail
predicates at toy size (8 validators, host verifier), and the whole
script under JAX_PLATFORMS=cpu, where it must say no chip was found and
exit non-zero before it generates or compiles anything.
"""

from __future__ import annotations

import os
import pathlib
import subprocess
import sys

import pytest

import chip_smoke as cs
from tendermint_tpu.services.verifier import HostBatchVerifier

_REPO = pathlib.Path(__file__).resolve().parents[1]
_TOY = dict(n_vals=8, n_blocks=20, big_heights=(4, 12), big_txs=40)
_DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture(scope="module")
def chain():
    return cs.build_chain(21, **_TOY)


@pytest.fixture(scope="module")
def tampered():
    return cs.build_chain(21, tamper_height=10, **_TOY)


def _fast_sync(chain) -> int:
    """Replay the chain through the real fast-sync reactor with the host
    verifier; the height the store reached."""
    from tendermint_tpu.abci.apps import KVStoreApp
    from tendermint_tpu.abci.client import local_client_creator
    from tendermint_tpu.blockchain.reactor import BlockchainReactor
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.db.kv import MemDB
    from tendermint_tpu.state import make_genesis_state

    state = make_genesis_state(MemDB(), chain.genesis)
    state.save()
    store = BlockStore(MemDB())
    conns = local_client_creator(KVStoreApp())()
    reactor = BlockchainReactor(
        state=state,
        store=store,
        app_conn=conns.consensus,
        fast_sync=True,
        verifier=HostBatchVerifier(),
        pipeline_depth=2,
    )
    reactor.pool.set_peer_height("src", len(chain.blocks))
    for h, block in enumerate(chain.blocks, start=1):
        reactor.pool._blocks[h] = (block, "src")
    try:
        reactor._try_sync()
    finally:
        reactor.on_stop()
        conns.close()
    if store.height:
        assert state.app_hash == chain.app_hashes[store.height - 1]
    return store.height


class TestChainBuilder:
    def test_seeded_and_self_consistent(self, chain):
        again = cs.build_chain(21, **_TOY)
        assert [b.hash() for b in again.blocks] == [b.hash() for b in chain.blocks]
        other = cs.build_chain(22, **_TOY)
        assert other.blocks[0].hash() != chain.blocks[0].hash()
        assert [len(b.data.txs) for b in chain.blocks][3] == 40
        # some lanes are absent in every commit, never the quorum
        for commit in chain.commits:
            absent = sum(1 for v in commit.precommits if v is None)
            assert 1 <= absent < len(commit.precommits) / 3
        for h in range(2, len(chain.blocks) + 1):
            assert chain.blocks[h - 1].last_commit is chain.commits[h - 2]
            assert chain.blocks[h - 1].header.app_hash == chain.app_hashes[h - 2]

    def test_host_reference_and_fast_sync_accept_the_clean_chain(self, chain):
        ref = cs.host_reference(chain, 19)
        assert ref == {"accepted": list(range(1, 20)), "refused": {}, "roots_ok": True}
        assert _fast_sync(chain) == 19

    def test_one_flipped_bit_is_refused_at_its_height(self, chain, tampered):
        height, idx = tampered.tampered
        assert height == 10
        # same chain up to the commit, self-consistent after it
        assert [b.hash() for b in tampered.blocks[:10]] == [
            b.hash() for b in chain.blocks[:10]
        ]
        assert tampered.blocks[10].hash() != chain.blocks[10].hash()
        good = chain.commits[9].precommits[idx].signature
        bad = tampered.commits[9].precommits[idx].signature
        assert bin(int.from_bytes(good, "big") ^ int.from_bytes(bad, "big")).count("1") == 1
        ref = cs.host_reference(tampered, 19)
        assert sorted(ref["refused"]) == [10]
        assert f"validator {idx}" in ref["refused"][10]
        assert ref["roots_ok"]
        assert _fast_sync(tampered) < 10


# -- predicates on observations ------------------------------------------------


def _metrics_text(**overrides) -> str:
    series = {
        'tendermint_verify_batch_size_bucket{backend="host",le="512"}': 7,
        'tendermint_verify_batch_size_bucket{backend="host",le="1024"}': 7,
        'tendermint_verify_batch_size_count{backend="host"}': 7,
        'tendermint_hash_batch_leaves_bucket{backend="host",le="4096"}': 90,
        'tendermint_hash_batch_leaves_count{backend="host"}': 90,
        'tendermint_verify_table_cache_total{event="host_build"}': 0,
        'tendermint_verify_table_cache_total{event="miss"}': 1,
        'tendermint_breaker_state{kind="verify"}': 0,
        'tendermint_breaker_state{kind="hash"}': 0,
        'tendermint_breaker_state{kind="tables"}': 0,
        'tendermint_breaker_transitions_total{kind="verify",to="open"}': 0,
        'tendermint_device_dispatch_failures_total{kind="tables"}': 0,
        'tendermint_device_fallback_calls_total{kind="verify"}': 0,
        'tendermint_device_primary_calls_total{kind="verify"}': 6,
        'tendermint_xla_persistent_cache_events_total{event="hit"}': 0,
        'tendermint_xla_persistent_cache_events_total{event="miss"}': 31,
        'tendermint_xla_compile_seconds_sum{fun="verify_tables_kernel"}': 61.5,
        'tendermint_xla_compile_seconds_count{fun="verify_tables_kernel"}': 3,
        'tendermint_xla_compile_seconds_sum{fun="add"}': 0.02,
        'tendermint_xla_compile_seconds_count{fun="add"}': 1,
    }
    series.update(overrides)
    lines = ["# HELP tendermint_breaker_state x", "# TYPE tendermint_breaker_state gauge"]
    lines += [f"{k} {v}" for k, v in series.items()]
    return "\n".join(lines) + "\n"


def _window(lo, hi, n, backend="tables", **extra) -> dict:
    k = hi - lo + 1
    pad = (-k) % 8 if k >= 8 else 0
    rec = {
        "kind": "tables", "backend": backend, "rows": k * n, "rows_padded": pad * n,
        "height_lo": lo, "height_hi": hi, "device_s": 0.05 * k,
    }
    rec.update(extra)
    return rec


def _good_obs(chain, upto=19) -> dict:
    n = len(chain.validators.validators)
    launches = [_window(1, 2, n), _window(3, 3, n), _window(4, 19, n)]
    # the toy chain's big blocks are small; stand in records of the
    # real size so the tree count has something to count
    launches += [{"kind": "hash", "backend": "device", "rows": 10_000}] * 2
    return {
        "height": upto,
        "health": {
            "device": {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1},
            "checks": {"breakers": {"ok": True}},
        },
        "launches": launches,
        "breakers": {"verifier": {"state": "closed", "fallback_calls": 0}},
        "metrics": cs.parse_metrics(_metrics_text()),
    }


class TestDeviceWorkPredicates:
    def test_a_run_where_the_device_did_the_work_passes(self, chain):
        assert cs.check_device_work(_good_obs(chain), chain, _DEVICE, 19) == []

    def test_ledger_coverage_and_shapes(self, chain):
        cov = cs.ledger_coverage(_good_obs(chain)["launches"])
        assert cov["device_heights"] == set(range(1, 20))
        assert cov["other_heights"] == set()
        assert cov["shapes"] == {
            (2, 8, "materialized"): 1, (1, 8, "materialized"): 1, (16, 8, "fused"): 1,
        }
        assert cov["mesh_widths"] == {1}

    @pytest.mark.parametrize(
        "mutate, needle",
        [
            (lambda o: o["health"]["device"].update(platform="cpu"), "health.device says"),
            (lambda o: o["launches"].pop(1), "covers heights [3]"),
            (lambda o: o["launches"].__setitem__(1, _window(3, 3, 8, backend="host")),
             "answered off the device"),
            (lambda o: o["launches"].__setitem__(2, _window(4, 10, 8)), "no fused launch"),
            (lambda o: o["launches"].pop(), "device tree launches"),
            (lambda o: o["launches"].__setitem__(2, _window(4, 19, 8, mesh_width=4)),
             "mesh_width"),
            (lambda o: o["breakers"]["verifier"].update(fallback_calls=1), "snapshot"),
        ],
    )
    def test_each_quiet_fallback_is_caught(self, chain, mutate, needle, monkeypatch):
        monkeypatch.setattr(cs, "TREE_LEAVES", 40)  # the toy chain's big blocks
        obs = _good_obs(chain)
        mutate(obs)
        bad = cs.check_device_work(obs, chain, _DEVICE, 19)
        assert any(needle in m for m in bad), bad

    @pytest.mark.parametrize(
        "series, needle",
        [
            ({'tendermint_verify_batch_size_count{backend="host"}': 8}, "commit-shaped"),
            ({'tendermint_hash_batch_leaves_count{backend="host"}': 91}, "8,192 leaves"),
            ({'tendermint_verify_table_cache_total{event="host_build"}': 1}, "host_build"),
            ({'tendermint_breaker_state{kind="hash"}': 2}, "hash breaker is not closed"),
            ({'tendermint_breaker_transitions_total{kind="verify",to="open"}': 1}, "moved"),
            ({'tendermint_device_dispatch_failures_total{kind="tables"}': 1}, "tables: device"),
            ({'tendermint_device_fallback_calls_total{kind="verify"}': 2}, "fell back"),
            ({'tendermint_device_primary_calls_total{kind="verify"}': 0}, "primary"),
        ],
    )
    def test_each_host_answer_in_the_metrics_is_caught(self, chain, series, needle):
        obs = _good_obs(chain)
        obs["metrics"] = cs.parse_metrics(_metrics_text(**series))
        bad = cs.check_device_work(obs, chain, _DEVICE, 19)
        assert any(needle in m for m in bad), bad

    def test_compile_report_separates_compile_from_run(self, chain):
        obs = _good_obs(chain)
        rep = cs.compile_report(obs["metrics"])
        assert rep["cache_hits"] == 0 and rep["cache_misses"] == 31
        assert rep["by_function"] == {
            "verify_tables_kernel": {"executables": 3, "seconds": 61.5}
        }
        assert cs.window_times(obs["launches"])["K=16"]["launches"] == 1


class TestIdentityAndRefusal:
    def _served(self, chain, upto=19) -> dict:
        """What `read_node` returns from a node that holds the chain,
        rendered with the RPC layer's own JSON shapes."""
        from tendermint_tpu.rpc.core import _block_json

        def commit_json(c):
            return {
                "commit": {
                    "block_id": {"hash": c.block_id.hash.hex()},
                    "precommits": [
                        None if v is None else {"signature": v.signature.hex()}
                        for v in c.precommits
                    ],
                }
            }

        raw = bytes(chain.blocks[3].data.txs[5])
        return {
            "height": upto,
            "status": {
                "sync_info": {
                    "latest_block_hash": chain.block_ids[upto - 1].hash.hex(),
                    "latest_app_hash": chain.app_hashes[upto - 1].hex(),
                    "catching_up": False,
                }
            },
            "block_hashes": {h: chain.block_ids[h - 1].hash.hex() for h in range(1, upto + 1)},
            "blocks": {h: _block_json(chain.blocks[h - 1]) for h in (1, 4, 16)},
            "commits": {h: commit_json(chain.commits[h - 1]) for h in (1, 4)},
            "validators": {
                "validators": [
                    {"pub_key": v.pub_key.data.hex()} for v in chain.validators.validators
                ]
            },
            "tx_reads": [
                {
                    "height": 4, "index": 5, "raw": raw.hex(),
                    "tx": {
                        "height": 4, "index": 5, "tx": raw.hex(),
                        "proof": {"root_hash": chain.blocks[3].header.data_hash.hex()},
                    },
                    "query": {"value": raw.split(b"=", 1)[1].hex()},
                }
            ],
        }

    def test_identity(self, chain):
        obs = self._served(chain)
        assert cs.check_identity(obs, chain, 19) == []
        obs["block_hashes"][7] = "00" * 20
        obs["status"]["sync_info"]["latest_app_hash"] = "ab"
        obs["tx_reads"][0]["query"]["value"] = "00"
        bad = cs.check_identity(obs, chain, 19)
        assert len(bad) == 3 and "block 7" in bad[0]
        assert cs.check_identity(self._served(chain, upto=12), chain, 19) == [
            "height 12 < 19"
        ]

    def test_refusal(self, tampered):
        ref = cs.host_reference(tampered, 19)
        obs = self._served(tampered, upto=8)
        obs["launches"] = [_window(1, 8, 8), _window(9, 19, 8)]
        obs["metrics"] = cs.parse_metrics(
            'tendermint_p2p_peer_misbehavior_total{kind="forged_block"} 1\n'
        )
        assert cs.check_refusal(obs, tampered, ref) == []
        late = dict(obs, height=12, block_hashes={})
        assert any("past the tampered commit" in m for m in cs.check_refusal(late, tampered, ref))
        quiet = dict(obs, metrics={})
        assert any("forged_block" in m for m in cs.check_refusal(quiet, tampered, ref))
        host = dict(obs, launches=[_window(1, 8, 8), _window(9, 19, 8, backend="host")])
        assert any("tampered height" in m for m in cs.check_refusal(host, tampered, ref))


def test_without_a_chip_the_script_says_so_and_fails():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(_REPO / "chip_smoke.py")],
        env=env,
        cwd=_REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == cs.EXIT_NO_CHIP != 0
    assert "no chip found" in proc.stderr
    assert proc.stdout.strip() == ""  # no result line, nothing generated


def test_alone_the_script_fails_without_a_result(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo it exits non-zero and prints no result, chip or no chip."""
    import shutil

    shutil.copy(_REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        cwd=tmp_path,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == cs.EXIT_NO_REPO != 0
    assert "not next to this script" in proc.stderr
    assert proc.stdout.strip() == ""
    assert os.listdir(tmp_path) == ["chip_smoke.py"]


def test_the_last_line_is_the_contracts_object_and_nothing_else():
    import json

    result = {
        "ok": True, "device": dict(_DEVICE), "seed": 21, "seconds": 992.6,
        "reduced": [{"kernel": "x", "reason": "time"}], "sync": {"height": 69},
    }
    line = cs.last_line(result)
    assert "\n" not in line
    obj = json.loads(line)
    assert list(obj) == ["ok", "device"] and obj["ok"] is True
    assert obj["device"] == _DEVICE and list(obj["device"]) == ["platform", "kind", "count"]
    assert type(obj["device"]["count"]) is int
    failed = json.loads(cs.last_line(dict(result, ok=False, failures=["sync: x"])))
    assert failed == {"ok": False, "device": _DEVICE}


def test_the_parent_side_never_imports_jax():
    """Chain building, the host reference and the predicates are what
    the parent runs; none may pull in JAX (it would take the chip from
    the children)."""
    code = (
        "import sys, chip_smoke as cs\n"
        "c = cs.build_chain(21, n_vals=8, n_blocks=6, big_heights=(2,), big_txs=20)\n"
        "cs.host_reference(c, 5); cs.check_device_work.__name__\n"
        "import tendermint_tpu.rpc.client\n"
        "print('jax' in sys.modules)\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=_REPO, capture_output=True, text=True, timeout=120
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "False"
