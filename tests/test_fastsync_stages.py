"""Fast-sync's stage clock: the
`tendermint_fastsync_stage_seconds{stage}` histogram with its two
counters, the CPU counter beside it
(`tendermint_fastsync_stage_cpu_seconds_total{stage}`: one `Stage`, two
clocks), and the one `fastsync.window` span a window. (The stopwatch
itself, `TRACER.stage`, is tested with the tracer in test_telemetry.py.)

All on the CPU with four validators and the host library passed as the
verifier: no kernel compiles, and a window's verify still goes through
the reactor's dispatch queue, so it leaves a launch record.
"""

from __future__ import annotations

import time

import pytest

from tendermint_tpu.abci.apps import KVStoreApp, PersistentKVStoreApp
from tendermint_tpu.abci.client import local_client_creator
from tendermint_tpu.blockchain import BlockchainReactor, BlockStore
from tendermint_tpu.blockchain.reactor import VERIFY_WINDOW
from tendermint_tpu.db.kv import MemDB
from tendermint_tpu.p2p import NodeInfo, Switch, connect_switches
from tendermint_tpu.services.verifier import HostBatchVerifier
from tendermint_tpu.state import make_genesis_state
from tendermint_tpu.telemetry import REGISTRY, TRACER
from tendermint_tpu.telemetry.launchlog import LAUNCHLOG
from tendermint_tpu.telemetry.metrics import (
    FASTSYNC_CHILD_STAGES,
    FASTSYNC_CUTS,
    FASTSYNC_STAGES,
    SPAN_CATALOG,
)

from tests.helpers import CHAIN_ID, THREAD_CLOCK_IS_FINE, ChainSim, cpu_slack
from tests.test_fastsync import _pipelined_reactor, _serving_node, wait_until

STAGE_SECONDS = "tendermint_fastsync_stage_seconds"
STAGE_CPU_SECONDS = "tendermint_fastsync_stage_cpu_seconds_total"
APPLY_STAGES = ("validate", "exec", "state_save")


class Readings:
    """The fast-sync series and window spans as they stand, so a test
    can say how far each rose (the registry and the tracer are the
    process's: other tests of this worker moved them before)."""

    def __init__(self) -> None:
        self.t0 = time.time()
        self.base = self._read()

    @staticmethod
    def _read() -> dict:
        series = REGISTRY.to_dict()
        out = {
            f"count.{s['labels']['stage']}": s["count"]
            for s in series[STAGE_SECONDS]["series"]
        }
        out.update(
            (f"sum.{s['labels']['stage']}", s["sum"])
            for s in series[STAGE_SECONDS]["series"]
        )
        out.update(
            (f"cpu.{s['labels']['stage']}", s["value"])
            for s in series[STAGE_CPU_SECONDS]["series"]
        )
        out.update(
            (f"cut.{s['labels']['cut']}", s["value"])
            for s in series["tendermint_fastsync_windows_total"]["series"]
        )
        out["blocks"] = REGISTRY.counter_value(
            "tendermint_fastsync_blocks_applied_total"
        )
        return out

    def rise(self) -> dict:
        now = self._read()
        return {k: now[k] - self.base[k] for k in now}

    def windows(self) -> list[dict]:
        return [
            s["attrs"]
            for s in TRACER.recent(prefix="fastsync.window")
            if s["start"] >= self.t0
        ]


def synced(sim: ChainSim, app=None):
    """`sim`'s chain through a fresh reactor's pipeline, no network."""
    reactor, _state, store = _pipelined_reactor(
        sim, depth=2, verifier=HostBatchVerifier(), app=app
    )
    reactor._try_sync()
    return reactor, store


def chain(n_blocks: int, app=None) -> ChainSim:
    sim = ChainSim(n_vals=4, app=app)
    for _ in range(n_blocks):
        sim.advance()
    return sim


class TestCatalog:
    def test_every_stage_and_cut_is_on_metrics_before_any_sync(self):
        text = REGISTRY.prometheus_text()
        assert FASTSYNC_CHILD_STAGES == ("index_rows",)
        for stage in FASTSYNC_STAGES + FASTSYNC_CHILD_STAGES:
            assert f'{STAGE_SECONDS}_count{{stage="{stage}"}}' in text
            assert f'{STAGE_CPU_SECONDS}{{stage="{stage}"}}' in text
        for cut in FASTSYNC_CUTS:
            assert f'tendermint_fastsync_windows_total{{cut="{cut}"}}' in text
        assert "tendermint_fastsync_blocks_applied_total" in text
        assert "fastsync.window" in SPAN_CATALOG


class TestWindows:
    def test_a_window_is_one_span_one_count_and_its_launchs_heights(self):
        sim = chain(48)
        before = Readings()
        reactor, store = synced(sim)
        assert store.height == 47
        rise, spans = before.rise(), before.windows()
        assert rise["blocks"] == 47 == reactor.blocks_synced
        # 1-16 and 17-32 are whole windows; the third ends where the pool does
        assert [(w["height_lo"], w["height_hi"], w["cut"]) for w in spans] == [
            (1, 16, "full"), (17, 32, "full"), (33, 47, "pool_gap")
        ]
        assert (rise["cut.full"], rise["cut.pool_gap"], rise["cut.boundary"]) == (2, 1, 0)
        assert VERIFY_WINDOW == 16
        # the launch record of a window carries the span's height_lo
        launched = {
            (r.get("height_lo"), r.get("height_hi"))
            for r in LAUNCHLOG.recent()
            if r["t"] >= before.t0
        }
        assert {(w["height_lo"], w["height_hi"]) for w in spans} <= launched
        # a span's seconds per stage are the histogram's
        for stage in ("part_set", "verify_submit", "verify_wait", "store", *APPLY_STAGES):
            assert sum(w[stage + "_s"] for w in spans) == pytest.approx(
                rise["sum." + stage], abs=1e-4
            )
        assert rise["count.store"] == rise["count.validate"] == 47
        # exec and state_save are two stretches a block each
        assert rise["count.exec"] == rise["count.state_save"] == 2 * 47
        assert rise["count.verify_submit"] == rise["count.verify_wait"] == 3

    def test_a_validator_set_change_cuts_the_window_at_the_boundary(self):
        sim = chain(20, app=PersistentKVStoreApp())
        pub = sim.state.validators.validators[0].pub_key.data.hex()
        sim.advance(txs=[f"val:{pub}/25".encode()])  # height 21 changes a power
        for _ in range(9):
            sim.advance()
        before = Readings()
        _reactor, store = synced(sim, app=PersistentKVStoreApp())
        assert store.height == 29
        rise, spans = before.rise(), before.windows()
        assert rise["blocks"] == 29
        assert sum(w["height_hi"] - w["height_lo"] + 1 for w in spans) == 29
        assert rise["cut.boundary"] >= 1
        assert sum(rise["cut." + c] for c in FASTSYNC_CUTS) == len(spans)
        assert [w["height_lo"] for w in spans] == sorted(w["height_lo"] for w in spans)

    def test_a_window_refused_at_its_join_is_counted_and_applies_its_verified_prefix(self):
        sim = chain(40)
        # height 39's commit rides in the last block, which nothing links
        # past: its signatures are well formed and wrong, so the window
        # is refused by the verdict and not at prep
        commit, other = sim.blocks[39].last_commit, sim.blocks[10].last_commit
        for i in range(3):
            commit.precommits[i] = commit.precommits[i].with_signature(
                other.precommits[i].signature
            )
        before = Readings()
        _reactor, store = synced(sim)
        # the verdict names entry 6, height 39: the six entries before it
        # are verified and applied (none was, until PR 47)
        assert store.height == 38
        rise, spans = before.rise(), before.windows()
        assert rise["blocks"] == 38
        assert [(w["height_lo"], w["cut"], "store_s" in w) for w in spans] == [
            (1, "full", True), (17, "full", True), (33, "pool_gap", True)
        ]
        assert sum(rise["cut." + c] for c in FASTSYNC_CUTS) == 3
        assert rise["count.store"] == 38


class TestApplyBlock:
    def test_without_a_stage_argument_nothing_is_recorded(self):
        before = Readings()
        chain(2)  # ChainSim applies as consensus does: no stage passed
        rise = before.rise()
        assert all(rise["count." + s] == 0 for s in FASTSYNC_STAGES)
        assert all(rise["cpu." + s] == 0 for s in FASTSYNC_STAGES + FASTSYNC_CHILD_STAGES)
        assert rise["blocks"] == 0 and before.windows() == []

    def test_the_stages_bracket_the_apply_in_order(self):
        from contextlib import contextmanager

        from tendermint_tpu.state import apply_block

        sim = chain(1)
        block, parts = sim.make_next_block()
        seen = []

        @contextmanager
        def stage(name):
            seen.append(name)
            yield

        apply_block(sim.state, block, parts.header, sim.conns.consensus, stage=stage)
        assert seen == ["validate", "exec", "state_save", "exec", "state_save"]
        assert sim.state.last_block_height == 2

    @pytest.mark.parametrize("kind", ["kv", "runlog"])
    def test_the_index_rows_are_a_stage_inside_state_save(self, kind, tmp_path, monkeypatch):
        """Building a block's tx index rows is timed apart from the
        write that follows it, inside the first `state_save`, once a
        block: the keys, the packed values and, for the run log, its
        value section and pointers, which `append` gets ready made."""
        from contextlib import contextmanager

        from tendermint_tpu.db.runlog import RunLog
        from tendermint_tpu.state import apply_block
        from tendermint_tpu.state.txindex import KVTxIndexer, RunTxIndexer
        from tendermint_tpu.types.tx import tx_hash

        sim = chain(1)
        block, parts = sim.make_next_block(txs=[b"a=1", b"b=2"])
        seen = []
        indexer = KVTxIndexer(MemDB()) if kind == "kv" else RunTxIndexer(str(tmp_path))
        real = RunLog.append

        def append(self, height, keys, values, starts):
            assert (type(keys), type(values), len(starts)) == (bytes, bytes, len(keys) // 32)
            seen.append("append")
            real(self, height, keys, values, starts)

        monkeypatch.setattr(RunLog, "append", append)

        @contextmanager
        def stage(name):
            seen.append("+" + name)
            yield
            seen.append("-" + name)

        apply_block(
            sim.state, block, parts.header, sim.conns.consensus,
            tx_indexer=indexer, stage=stage,
        )
        write = ["append"] if kind == "runlog" else []
        assert seen[4 : 8 + len(write)] == [
            "+state_save", "+index_rows", "-index_rows", *write, "-state_save"
        ]
        assert [n for n in seen if n.startswith("+")] == [
            "+validate", "+exec", "+state_save", "+index_rows", "+exec", "+state_save"
        ]
        assert indexer.get(tx_hash(b"b=2")).index == 1
        # without a stopwatch (consensus) the rows are built all the same
        indexer.add_batch(*_block_of(sim, [b"c=3"]))
        assert indexer.get(tx_hash(b"c=3")).height == 9
        indexer.close()


def _block_of(sim, txs):
    """A block and its responses as `add_batch` reads them."""
    from types import SimpleNamespace

    from tendermint_tpu.abci.types import Result

    block = SimpleNamespace(header=SimpleNamespace(height=9), data=SimpleNamespace(txs=txs))
    return block, SimpleNamespace(deliver_tx=[Result(0, b"", "")] * len(txs))


class TestOverTheWire:
    def test_every_stage_is_observed_while_a_fresh_node_syncs(self):
        sim = ChainSim(n_vals=4)
        store = BlockStore(MemDB())
        for _ in range(24):
            block = sim.advance()
            store.save_block(block, block.make_part_set(), sim.commits[-1])
        before = Readings()
        server = _serving_node(sim, store)
        fresh_state = make_genesis_state(MemDB(), sim.genesis)
        fresh_state.save()
        fresh_store = BlockStore(MemDB())
        reactor = BlockchainReactor(
            state=fresh_state,
            store=fresh_store,
            app_conn=local_client_creator(KVStoreApp())().consensus,
            fast_sync=True,
            verifier=HostBatchVerifier(),
        )
        client = Switch(NodeInfo(node_id="fresh", moniker="fresh", chain_id=CHAIN_ID))
        client.add_reactor("blockchain", reactor)
        client.start()
        try:
            connect_switches(server, client)
            wait_until(lambda: fresh_store.height >= 23, timeout=30, msg="fresh node synced")
            wait_until(lambda: not reactor.fast_sync, timeout=10, msg="caught up")
        finally:
            server.stop()
            client.stop()
        rise, spans = before.rise(), before.windows()
        for stage in FASTSYNC_STAGES:
            assert rise["count." + stage] > 0, stage
            assert rise["sum." + stage] > 0, stage
            # the thread's CPU clock beside the wall's, over the same
            # stretches: it rose (where the clock steps finely enough to
            # tell), and by no more than the wall did, give or take a
            # step of that clock a stretch
            slack = cpu_slack(rise["count." + stage])
            assert 0 <= rise["cpu." + stage] <= rise["sum." + stage] + slack, stage
            assert rise["cpu." + stage] > 0 or not THREAD_CLOCK_IS_FINE, stage
        # the idle tick sleeps: next to no CPU in it
        assert rise["cpu.starved"] < 0.5 * rise["sum.starved"]
        assert rise["count.decode"] == 24  # one a block_response
        assert rise["blocks"] == fresh_store.height == reactor.blocks_synced
        assert sum(w["height_hi"] - w["height_lo"] + 1 for w in spans) == fresh_store.height
        assert sum(rise["cut." + c] for c in FASTSYNC_CUTS) == len(spans) >= 2
