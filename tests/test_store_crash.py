"""A block's three durable points under SIGKILL, and how many SQLite
transactions a block costs.

A child process saves blocks and states through `BlockStore` and
`State` on SQLite files, in the order fast-sync and consensus do
(`save_block`, then `apply_block`: ABCI responses, the app's commit, the
state), and prints a line after each call has returned. The parent
kills it with SIGKILL at a random moment: no `close()`, no checkpoint,
the WAL as the last commit left it. What it then finds on the files has
to hold what `blockchain/store.py` and `db/kv.py` promise.
"""

from __future__ import annotations

import os
import random
import signal
import subprocess
import sys
import textwrap
import threading

import pytest

from tendermint_tpu.abci.apps import KVStoreApp
from tendermint_tpu.abci.client import local_client_creator
from tendermint_tpu.blockchain import BlockchainReactor, BlockStore
from tendermint_tpu.codec import Reader
from tendermint_tpu.db.kv import SQLiteDB
from tendermint_tpu.services.verifier import HostBatchVerifier
from tendermint_tpu.state import apply_block, load_state, make_genesis_state
from tendermint_tpu.state.txindex import RunTxIndexer
from tendermint_tpu.types.tx import tx_hash

from tests.helpers import THREAD_CLOCK_IS_FINE, ChainSim, cpu_slack
from tests.test_db_batch import commits

def commits_timed(db_name: str) -> tuple[float, float, float]:
    """`tendermint_db_commit_seconds{db}`'s count and sum and the CPU
    counter beside it, as they stand."""
    from tendermint_tpu.telemetry import REGISTRY

    hist = [
        s for s in REGISTRY.to_dict()["tendermint_db_commit_seconds"]["series"]
        if s["labels"]["db"] == db_name
    ]
    cpu = REGISTRY.counter_value("tendermint_db_commit_cpu_seconds_total", db=db_name)
    return (hist[0]["count"], hist[0]["sum"], cpu) if hist else (0, 0.0, cpu)


_CHILD = textwrap.dedent(
    """
    import os, sys
    sys.path.insert(0, {repo!r})
    os.chdir({repo!r})
    home = {home!r}
    from tendermint_tpu.blockchain import BlockStore
    from tendermint_tpu.db.kv import SQLiteDB
    from tendermint_tpu.services.verifier import HostBatchVerifier
    from tendermint_tpu.state import apply_block
    from tests.helpers import THREAD_CLOCK_IS_FINE, ChainSim, cpu_slack

    sim = ChainSim(n_vals=4, db=SQLiteDB(home + "/state.db"))
    store = BlockStore(SQLiteDB(home + "/blockstore.db"))
    verifier = HostBatchVerifier()
    print("ready", flush=True)
    while True:
        h = sim.state.last_block_height + 1
        block, parts = sim.make_next_block([b"k%d-%d=v" % (h, i) for i in range(3)])
        commit = sim._commit_for(block, parts)
        store.save_block(block, parts, commit)
        print("stored", h, flush=True)
        apply_block(sim.state, block, parts.header, sim.conns.consensus, verifier=verifier)
        print("applied", h, flush=True)
        sim.blocks.append(block)
        sim.commits.append(commit)
    """
)


class TestSigkill:
    @pytest.mark.parametrize("seed", [1, 2, 3, 4])
    def test_what_was_acknowledged_is_there_and_every_height_loads_whole(self, tmp_path, seed):
        home = str(tmp_path)
        script = tmp_path / "writer.py"
        script.write_text(_CHILD.format(repo=os.getcwd(), home=home))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, str(script)], env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
        )
        # the test's own time limit: a child that hangs is killed, and the
        # lines the parent waits for then end
        overdue = threading.Timer(90.0, proc.kill)
        overdue.start()
        try:
            assert proc.stdout.readline().strip() == "ready", proc.stderr.read()
            # some blocks in, then a moment no line of the child knows of
            for _ in range(2 * random.Random(seed).randint(1, 6)):
                proc.stdout.readline()
            delay = random.Random(seed * 7919).uniform(0.0, 0.25)
            try:
                proc.wait(timeout=delay)
            except subprocess.TimeoutExpired:
                pass
            assert proc.poll() is None, proc.stderr.read()
            proc.send_signal(signal.SIGKILL)
            out, _ = proc.communicate(timeout=60)
        finally:
            still_in_time = overdue.is_alive()
            overdue.cancel()
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=60)
        assert still_in_time and proc.returncode == -signal.SIGKILL
        # the lines the parent read one by one are acknowledged as well,
        # and the child wrote its lines in order: the last of each kind counts
        acked = {"stored": 0, "applied": 0}
        for line in out.splitlines():
            kind, _, height = line.partition(" ")
            if kind in acked and height.isdigit():
                acked[kind] = max(acked[kind], int(height))

        # reopened as a restart would: the WAL left behind is replayed
        store_db = SQLiteDB(home + "/blockstore.db")
        state_db = SQLiteDB(home + "/state.db")
        try:
            store = BlockStore(store_db)
            state = load_state(state_db)
            assert store.height >= max(acked["stored"], 1), (store.height, acked)
            assert state.last_block_height >= acked["applied"], (state.last_block_height, acked)
            # the store leads the state by a block at most
            assert state.last_block_height in (store.height, store.height - 1)
            for h in range(1, store.height + 1):
                meta = store.load_block_meta(h)
                assert meta is not None and meta.header.height == h
                for i in range(meta.block_id.parts_header.total):
                    assert store.load_block_part(h, i) is not None
                block = store.load_block(h)
                assert block.hash() == meta.block_id.hash
                seen = store.load_seen_commit(h)
                assert seen.height() == h and seen.block_id == meta.block_id
                canonical = store.load_block_commit(h - 1)
                if h > 1:
                    assert canonical.height() == h - 1
                    assert canonical.block_id == block.header.last_block_id
            # atomic per block: no row of a height above the watermark
            above = store.height + 1
            for key in (b"H:%d" % above, b"P:%d:0" % above, b"SC:%d" % above, b"C:%d" % store.height):
                assert store_db.get(key) is None, key
            # the responses of every applied height came before its state,
            # and the validators of the next height with it
            for h in range(1, state.last_block_height + 1):
                assert state.load_abci_responses(h) is not None
            assert state.load_validators(state.last_block_height + 1).hash() == state.validators.hash()
        finally:
            store_db.close()
            state_db.close()


class _OrderCheckingApp(KVStoreApp):
    """At the app's `Commit` of height h, looks at the files through
    connections of its own: the store's watermark and the ABCI responses
    of h have to be there already, the state still at h - 1."""

    def __init__(self, home: str) -> None:
        super().__init__()
        self.home = home
        self.height = 0
        self.seen: list = []

    def commit(self):
        self.height += 1
        store_db = SQLiteDB(self.home + "/blockstore.db")
        state_db = SQLiteDB(self.home + "/state.db")
        try:
            self.seen.append((
                self.height,
                BlockStore(store_db).height,
                state_db.has(b"abciResponsesKey:%d" % self.height),
                load_state(state_db).last_block_height,
            ))
        finally:
            store_db.close()
            state_db.close()
        return super().commit()


class TestABlocksTransactions:
    N_BLOCKS = 40

    def _files(self, tmp_path):
        return {
            name: SQLiteDB(str(tmp_path / f"{name}.db"))
            for name in ("blockstore", "state")
        }

    def test_a_fast_synced_block_is_four_commits(self, tmp_path):
        """blockstore 1 (rows and watermark), state 2 (ABCI responses;
        validators pointer and state), txindex 1 (its rows: one record
        appended to the run log the node builds beside its files). A
        fifth fails here, not in a chip run."""
        sim = ChainSim(n_vals=4)
        for h in range(self.N_BLOCKS + 1):
            sim.advance(txs=[b"k%d-%d=v" % (h, i) for i in range(3)])
        dbs = self._files(tmp_path)
        indexer = RunTxIndexer(str(tmp_path))
        try:
            state = make_genesis_state(dbs["state"], sim.genesis)
            state.save()
            store = BlockStore(dbs["blockstore"])
            reactor = BlockchainReactor(
                state=state, store=store,
                app_conn=local_client_creator(KVStoreApp())().consensus,
                fast_sync=True, verifier=HostBatchVerifier(), pipeline_depth=2,
                tx_indexer=indexer,
            )
            reactor.pool.set_peer_height("srv", len(sim.blocks))
            for h, b in enumerate(sim.blocks, start=1):
                reactor.pool._blocks[h] = (b, "srv")
            names = (*dbs, "txindex")
            before = {name: commits(name) for name in names}
            timed_before = {name: commits_timed(name) for name in names}
            reactor._try_sync()
            rise = {name: commits(name) - before[name] for name in names}
            n = self.N_BLOCKS
            assert store.height == n == state.last_block_height == reactor.blocks_synced
            assert (rise["blockstore"], rise["state"], rise["txindex"]) == (n, 2 * n, n)
            # each is timed where it happens, and only it: the histogram's
            # count is the counter, its CPU no more than its wall (give
            # or take a step of the thread's clock a commit)
            for name in names:
                count, seconds, cpu = (
                    now - was for now, was in zip(commits_timed(name), timed_before[name])
                )
                assert count == rise[name], name
                assert 0 <= cpu <= seconds + cpu_slack(count), name
                assert cpu > 0 or not THREAD_CLOCK_IS_FINE, name
            assert indexer.get(tx_hash(b"k7-2=v")).height == 8
        finally:
            indexer.close()
            for db in dbs.values():
                db.close()

    def test_watermark_then_responses_then_app_commit_then_state(self, tmp_path):
        sim = ChainSim(n_vals=4)
        for h in range(5):
            sim.advance(txs=[b"k%d=v" % h])
        dbs = self._files(tmp_path)
        indexer = RunTxIndexer(str(tmp_path))
        try:
            state = make_genesis_state(dbs["state"], sim.genesis)
            state.save()
            store = BlockStore(dbs["blockstore"])
            app = _OrderCheckingApp(str(tmp_path))
            conns = local_client_creator(app)()
            for i in range(4):
                block = sim.blocks[i]
                parts = block.make_part_set()
                store.save_block(block, parts, sim.commits[i])
                apply_block(
                    state, block, parts.header, conns.consensus,
                    verifier=HostBatchVerifier(), tx_indexer=indexer,
                )
                # back from the call: the state of this height is on the file
                other = SQLiteDB(str(tmp_path / "state.db"))
                try:
                    assert load_state(other).last_block_height == i + 1
                finally:
                    other.close()
            assert app.seen == [(h, h, True, h - 1) for h in range(1, 5)]
        finally:
            indexer.close()
            for db in dbs.values():
                db.close()


class TestTheStoreHasOneWayToWrite:
    def _chain(self, n: int) -> ChainSim:
        sim = ChainSim(n_vals=4)
        for h in range(n):
            sim.advance(txs=[b"k%d=v" % h])
        return sim

    def test_each_commit_is_encoded_once_and_the_rows_are_its_encoding(self):
        """Fast-sync's order: the seen commit of block H is `last_commit`
        of block H+1, the same object, and none of its votes is encoded
        at all: `Vote.decode` kept the canonical bytes a peer sent, and
        the part set and both rows are made of them."""
        from tendermint_tpu.db.kv import MemDB
        from tendermint_tpu.telemetry import REGISTRY
        from tendermint_tpu.types.block import Block, Commit

        def count(what: str) -> float:
            return REGISTRY.counter_value(f"tendermint_vote_{what}_total")

        sim = self._chain(8)
        wires = [b.encode() for b in sim.blocks]
        last_seen = sim.commits[7].encode()  # the sim's own votes, encoded here
        encodes, kept = count("encodes"), count("wire_kept")
        # as a peer sends them; block 1's last commit is empty, four votes
        # a block after it
        blocks = [Block.decode(w) for w in wires]
        assert count("wire_kept") - kept == 7 * 4
        part_sets = [b.make_part_set() for b in blocks]  # encodes the block
        assert [ps.header for ps in part_sets] == [
            b.make_part_set().header for b in sim.blocks
        ]
        db = MemDB()
        store = BlockStore(db)
        for i in range(7):
            store.save_block(blocks[i], part_sets[i], blocks[i + 1].last_commit)
        assert count("encodes") - encodes == 0
        for h in range(1, 8):
            assert db.get(b"SC:%d" % h) == sim.blocks[h].last_commit.encode()
            # the canonical commit of h comes with block h + 1
            assert db.get(b"C:%d" % (h - 1)) == sim.blocks[h - 1].last_commit.encode()
        # a commit that only looks the same has its own votes, with their
        # own kept bytes: decoded, not encoded
        other = Commit.decode_from(Reader(last_seen))
        store.save_block(blocks[7], part_sets[7], other)
        assert count("wire_kept") - kept == 8 * 4
        assert db.get(b"SC:8") == last_seen
        assert db.get(b"C:7") == db.get(b"SC:7")
        # and what the store hands back is made of kept bytes too
        assert store.load_seen_commit(8).encode() == last_seen
        assert count("encodes") - encodes == 0

    def test_bootstrap_and_prune_are_one_transaction_each(self, tmp_path):
        sim = self._chain(12)
        db = SQLiteDB(str(tmp_path / "pruned.db"))
        try:
            store = BlockStore(db)
            before = commits("pruned")
            store.bootstrap([(sim.blocks[i], sim.commits[i]) for i in range(4, 11)])
            assert (store.base, store.height) == (5, 11)
            assert commits("pruned") - before == 1
            assert store.prune(9) == 4
            assert (store.base, store.height) == (9, 11)
            assert commits("pruned") - before == 2
            assert store.load_block(8) is None and db.get(b"P:8:0") is None
            assert store.load_block(9).hash() == sim.blocks[8].hash()
            assert store.load_block_commit(8) is not None and store.load_block_commit(7) is None
            again = BlockStore(db)
            assert (again.base, again.height) == (9, 11)
        finally:
            db.close()
