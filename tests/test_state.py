"""State, execution, ABCI apps, DB backends, tx indexing, fail points."""

import os
import subprocess
import sys

import pytest

from tendermint_tpu.abci.apps import CounterApp, KVStoreApp, PersistentKVStoreApp
from tendermint_tpu.abci.client import local_client_creator
from tendermint_tpu.abci.types import CodeType
from tendermint_tpu.db.kv import MemDB, SQLiteDB
from tendermint_tpu.state import load_state, make_genesis_state
from tendermint_tpu.state.state import ABCIResponses
from tendermint_tpu.state.txindex import KVTxIndexer
from tendermint_tpu.types.errors import ValidationError
from tendermint_tpu.types.tx import tx_hash

from tests.helpers import ChainSim, make_genesis


class TestDB:
    def test_memdb_roundtrip_and_prefix_iterate(self):
        db = MemDB()
        db.set(b"a:1", b"x")
        db.set(b"a:2", b"y")
        db.set(b"b:1", b"z")
        assert db.get(b"a:1") == b"x"
        assert db.get(b"missing") is None
        assert list(db.iterate(b"a:")) == [(b"a:1", b"x"), (b"a:2", b"y")]
        db.delete(b"a:1")
        assert not db.has(b"a:1")

    def test_sqlite_roundtrip_persistence(self, tmp_path):
        path = str(tmp_path / "kv.db")
        db = SQLiteDB(path)
        db.set(b"k1", b"v1")
        db.set_sync(b"k2", b"v2")
        db.delete(b"k1")
        db.close()
        db2 = SQLiteDB(path)
        assert db2.get(b"k1") is None
        assert db2.get(b"k2") == b"v2"
        assert list(db2.iterate()) == [(b"k2", b"v2")]
        db2.close()


class TestApps:
    def test_kvstore(self):
        app = KVStoreApp()
        conns = local_client_creator(app)()
        assert conns.mempool.check_tx_async(b"name=satoshi").is_ok
        conns.consensus.deliver_tx_async(b"name=satoshi")
        h1 = conns.consensus.commit_sync().data
        assert h1 != b""
        q = conns.query.query_sync("/key", b"name")
        assert q.value == b"satoshi"
        conns.consensus.deliver_tx_async(b"other=thing")
        assert conns.consensus.commit_sync().data != h1

    def test_counter_serial_nonce(self):
        app = CounterApp(serial=True)
        conns = local_client_creator(app)()
        assert conns.consensus.deliver_tx_async(b"\x00").is_ok
        res = conns.consensus.deliver_tx_async(b"\x00")
        assert res.code == CodeType.BAD_NONCE
        assert conns.consensus.deliver_tx_async(b"\x01").is_ok
        assert conns.mempool.check_tx_async(b"\x00").code == CodeType.BAD_NONCE
        assert conns.mempool.check_tx_async(b"\x05").is_ok  # check allows >=

    def test_persistent_kvstore_reload(self):
        db = MemDB()
        app = PersistentKVStoreApp(db)
        app.deliver_tx(b"k=v")
        app.end_block(3)
        app.commit()
        app2 = PersistentKVStoreApp(db)
        assert app2.info().last_block_height == 3
        assert app2.query("/key", b"k").value == b"v"


class TestGenesisState:
    def test_make_save_load_roundtrip(self):
        db = MemDB()
        gen, _ = make_genesis(4)
        st = make_genesis_state(db, gen)
        assert st.last_block_height == 0
        assert st.validators.size() == 4
        assert st.last_validators.size() == 0
        st.save()
        st2 = load_state(db)
        assert st2 is not None and st2.equals(st)

    def test_load_missing_returns_none(self):
        assert load_state(MemDB()) is None


class TestApplyBlock:
    def test_three_heights_with_real_commits(self):
        sim = ChainSim(n_vals=4)
        sim.advance(txs=[b"a=1"])
        assert sim.state.last_block_height == 1
        app_hash_1 = sim.state.app_hash
        assert app_hash_1 != b""
        sim.advance(txs=[b"b=2"])
        app_hash_2 = sim.state.app_hash
        assert app_hash_2 != app_hash_1
        sim.advance()
        assert sim.state.last_block_height == 3
        assert sim.state.app_hash == app_hash_2  # height-3 block had no txs
        assert sim.state.last_validators.hash() == sim.state.validators.hash()
        # state persisted each height
        st = load_state(sim.db)
        assert st.last_block_height == 3

    def test_validate_block_rejections(self):
        sim = ChainSim(n_vals=4)
        sim.advance()
        block, ps = sim.make_next_block()
        block.header.height += 1  # wrong height
        from tendermint_tpu.state import validate_block

        with pytest.raises(ValidationError, match="wrong height"):
            validate_block(sim.state, block, None)

        block2, _ = sim.make_next_block()
        block2.header.app_hash = b"\x01" * 20
        block2.header.data_hash = b""  # force refill? header already filled
        with pytest.raises(ValidationError, match="app_hash"):
            validate_block(sim.state, block2, None)

    def test_bad_last_commit_signature_rejected(self):
        sim = ChainSim(n_vals=4)
        sim.advance()
        # tamper a commit signature, then try to apply height 2
        block, ps = sim.make_next_block()
        pc = block.last_commit.precommits[0]
        object.__setattr__(pc, "signature", bytes(64))
        block.header.last_commit_hash = b""
        block.fill_header()
        from tendermint_tpu.state import validate_block

        with pytest.raises(ValidationError):
            validate_block(sim.state, block, None)

    def test_tx_indexer_batch(self):
        db = MemDB()
        sim = ChainSim(n_vals=4)
        idx = KVTxIndexer(db)
        sim.advance(txs=[b"k1=v1", b"k2=v2"], tx_indexer=idx)
        tr = idx.get(tx_hash(b"k1=v1"))
        assert tr is not None and tr.height == 1 and tr.index == 0
        assert idx.get(b"\x00" * 20) is None


class TestValidatorChanges:
    def test_end_block_diffs_rotate_in(self):
        from tendermint_tpu.crypto.keys import gen_priv_key

        db = MemDB()
        sim = ChainSim(n_vals=4, app=PersistentKVStoreApp(db))
        new_key = gen_priv_key(b"\x99" * 32)
        hash_before = sim.state.validators.hash()
        sim.advance(txs=[b"val:" + new_key.pub_key.data.hex().encode() + b"/7"])
        # the diff applies to the validator set for the next height
        assert sim.state.validators.size() == 5
        assert sim.state.last_validators.hash() == hash_before
        assert sim.state.last_height_validators_changed == 2
        _, v = sim.state.validators.get_by_address(new_key.pub_key.address)
        assert v is not None and v.voting_power == 7

    def test_historical_validators_with_compression(self):
        sim = ChainSim(n_vals=3)
        for _ in range(4):
            sim.advance()
        vs1 = sim.state.load_validators(1)
        vs4 = sim.state.load_validators(4)
        assert vs1.hash() == vs4.hash() == sim.state.validators.hash()
        with pytest.raises(ValidationError):
            sim.state.load_validators(99)


class TestABCIResponses:
    def test_save_load(self):
        sim = ChainSim(n_vals=4)
        sim.advance(txs=[b"x=y"])
        res = sim.state.load_abci_responses(1)
        assert res is not None
        assert res.height == 1 and len(res.deliver_tx) == 1
        assert res.deliver_tx[0].is_ok
        assert sim.state.load_abci_responses(9) is None


class TestFailPoints:
    def test_fail_index_kills_process_at_each_point(self, tmp_path):
        script = tmp_path / "crash.py"
        script.write_text(
            "import sys; sys.path.insert(0, %r)\n"
            "from tests.helpers import ChainSim\n"
            "sim = ChainSim(n_vals=2)\n"
            "sim.advance(txs=[b'a=1'])\n"
            "print('SURVIVED')\n" % os.getcwd()
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        # 4 fail points in apply_block: indices 0..3 must die, 4 survives
        for idx in range(4):
            env["FAIL_TEST_INDEX"] = str(idx)
            p = subprocess.run(
                [sys.executable, str(script)], env=env, capture_output=True, text=True
            )
            assert p.returncode == 1, (idx, p.stdout, p.stderr)
            assert "SURVIVED" not in p.stdout
        env["FAIL_TEST_INDEX"] = "4"
        p = subprocess.run(
            [sys.executable, str(script)], env=env, capture_output=True, text=True
        )
        assert p.returncode == 0 and "SURVIVED" in p.stdout, p.stderr


def _plain_valset(vs) -> dict:
    return {
        "validators": [
            {
                "address": v.address.hex(),
                "pub_key": v.pub_key.data.hex(),
                "voting_power": v.voting_power,
                "accum": v.accum,
            }
            for v in vs.validators
        ]
    }


def _plain_state_document(state) -> bytes:
    """The state document as `json.dumps` writes it from plain dicts: the
    form on disk in every node's state DB and inside every snapshot."""
    import json

    return json.dumps(
        {
            "chain_id": state.chain_id,
            "consensus_params": state.consensus_params.to_dict(),
            "last_block_height": state.last_block_height,
            "last_block_id": {
                "hash": state.last_block_id.hash.hex(),
                "parts": {
                    "total": state.last_block_id.parts_header.total,
                    "hash": state.last_block_id.parts_header.hash.hex(),
                },
            },
            "last_block_time": state.last_block_time,
            "validators": _plain_valset(state.validators),
            "last_validators": _plain_valset(state.last_validators),
            "last_height_validators_changed": state.last_height_validators_changed,
            "app_hash": state.app_hash.hex(),
        },
        sort_keys=True,
    ).encode()


class TestStateDocumentBytes:
    """`State.to_json` formats what a block cannot change once a
    membership; the bytes must stay `json.dumps`'s, whatever the sets
    have been through since the static half was kept."""

    SHAPES = ("genesis", "rotated", "extreme_accums", "after_apply_changes")

    @staticmethod
    def _state(n: int, shape: str):
        from tendermint_tpu.state.state import ABCIResponses
        from tendermint_tpu.abci.types import Validator as ABCIValidator
        from tendermint_tpu.types.block import Header
        from tendermint_tpu.types.part_set import PartSetHeader
        from tendermint_tpu.types.validator_set import Validator, ValidatorSet

        from tests.helpers import det_priv_keys

        genesis, _ = make_genesis(n, chain_id="bytes-é-chain")
        state = make_genesis_state(MemDB(), genesis)
        if shape == "genesis":
            return state
        state.to_json()  # the static half is kept from here on

        def advance(changes=()):
            height = state.last_block_height + 1
            header = Header(
                chain_id=state.chain_id, height=height, time=height * 10**9, num_txs=0,
                last_block_id=state.last_block_id, validators_hash=state.validators.hash(),
            )
            state.set_block_and_validators(
                header,
                PartSetHeader(1, b"\x07" * 20),
                ABCIResponses(height=height, end_block_changes=list(changes)),
            )
            state.app_hash = bytes([height]) * 20

        for _ in range(3):
            advance()
        if shape == "extreme_accums":
            accums = [-(2**63) - 1, 2**53 + 1, 2**64 + 3, -1, 0]
            state.validators = ValidatorSet(
                [
                    Validator(v.address, v.pub_key, v.voting_power, accums[i % 5] + i)
                    for i, v in enumerate(state.validators.validators)
                ]
            )
            state.to_json()
            advance()
        elif shape == "after_apply_changes":
            members = state.validators.validators
            changes = [
                ABCIValidator(det_priv_keys(n + 1)[n].pub_key.data, 7),  # added
                ABCIValidator(members[0].pub_key.data, 33),  # re-powered
            ]
            if n > 1:
                changes.append(ABCIValidator(members[-1].pub_key.data, 0))  # removed
            advance(changes)
            assert state.validators.size() == n + 1 - (n > 1)
            assert state.last_validators.size() == n
        return state

    @pytest.mark.parametrize("shape", SHAPES)
    @pytest.mark.parametrize("n", [1, 4, 100])
    def test_the_bytes_are_json_dumps_own(self, n, shape):
        from tendermint_tpu.state.state import State

        state = self._state(n, shape)
        for _ in range(2):  # built, then from what was kept
            assert state.to_json() == _plain_state_document(state)
        again = State.from_json(state.to_json(), db=state.db)
        assert again.to_json() == state.to_json() and again.equals(state)
        assert again.validators.hash() == state.validators.hash()
        # a copy writes the same bytes and goes on rotating apart
        copy = state.copy()
        copy.validators.increment_accum(1)
        assert copy.to_json() == _plain_state_document(copy)
        # (a lone validator's accum stays where it is)
        assert (copy.to_json() != state.to_json()) == (state.validators.size() > 1)
        assert state.to_json() == _plain_state_document(state)

    @pytest.mark.parametrize("n", [1, 4, 100])
    def test_the_validators_rows_are_json_dumps_own(self, n):
        import json

        state = self._state(n, "after_apply_changes")
        changed = state.last_height_validators_changed
        assert changed == state.last_block_height + 1
        full = json.dumps(
            {"last_changed": changed, "validators": _plain_valset(state.validators)},
            sort_keys=True,
        ).encode()
        assert state._validators_info_row() == (b"validatorsKey:%d" % changed, full)
        state.save()
        state.save_validators_full()
        assert state.db.get(b"validatorsKey:%d" % changed) == full
        state.last_block_height += 1
        pointer = json.dumps({"last_changed": changed}, sort_keys=True).encode()
        assert state._validators_info_row() == (b"validatorsKey:%d" % (changed + 1), pointer)
        state.save()
        assert state.load_validators(changed + 1).hash() == state.validators.hash()


class TestValsetRootKept:
    """`tendermint_valset_hashes_total` counts roots computed. A node that
    applies a chain computes one a validator set, not one a block."""

    N_VALS, N_BLOCKS = 16, 20
    NAME = "tendermint_valset_hashes_total"

    def _apply_chain(self, change_at: int | None):
        """Build a chain on one node, apply it on a fresh one through
        `apply_block`; the counter's rise after each block."""
        from tendermint_tpu.crypto.keys import gen_priv_key
        from tendermint_tpu.services.verifier import HostBatchVerifier
        from tendermint_tpu.types import PrivValidator
        from tendermint_tpu.state import apply_block
        from tendermint_tpu.telemetry import REGISTRY

        source = ChainSim(n_vals=self.N_VALS, app=PersistentKVStoreApp(MemDB()))
        key = gen_priv_key(b"\x42" * 32)
        newcomer = key.pub_key
        source.privs.append(PrivValidator(key))  # signs once it is in the set
        for height in range(1, self.N_BLOCKS + 1):
            txs = [b"val:" + newcomer.data.hex().encode() + b"/9"] if height == change_at else []
            source.advance(txs=txs)

        state = make_genesis_state(MemDB(), source.genesis)
        conns = local_client_creator(PersistentKVStoreApp(MemDB()))()
        start = REGISTRY.counter_value(self.NAME)
        rises = []
        for block in source.blocks:
            apply_block(
                state, block, block.make_part_set().header, conns.consensus,
                verifier=HostBatchVerifier(),
            )
            rises.append(int(REGISTRY.counter_value(self.NAME) - start))
        assert state.equals(source.state)
        return rises, state

    def test_a_static_chain_costs_one_root(self):
        from tendermint_tpu.telemetry import REGISTRY

        rises, state = self._apply_chain(change_at=None)
        assert len(rises) == self.N_BLOCKS
        assert rises[0] <= 1 and rises[-1] == rises[0]
        before = REGISTRY.counter_value(self.NAME)
        assert state.last_validators.hash() == state.validators.copy().hash()
        assert REGISTRY.counter_value(self.NAME) == before  # a kept root is free

    def test_a_membership_change_costs_one_more_and_the_next_header_still_checks(self):
        change_at = 10
        rises, state = self._apply_chain(change_at=change_at)
        # block `change_at` changes the set for height change_at + 1, whose
        # header carries the new root: computed once, with the change
        # (state/state.py times both as one `valset.change` span), and
        # validate_block of the next height checks against the kept root
        assert rises[change_at - 2] == rises[0] <= 1
        assert rises[change_at - 1] == rises[0] + 1 == rises[change_at] == rises[-1]
        assert state.validators.size() == self.N_VALS + 1
        assert state.last_height_validators_changed == change_at + 1

    def test_the_counter_is_cataloged_and_documented(self):
        import pathlib

        from tendermint_tpu.telemetry import REGISTRY

        docs = pathlib.Path(__file__).resolve().parents[1] / "docs" / "OBSERVABILITY.md"
        assert self.NAME in REGISTRY.prometheus_text()
        assert self.NAME in docs.read_text()
