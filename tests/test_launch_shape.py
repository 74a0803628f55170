"""The launch shape of a commit window (PR 27): `commit_launch_shape` as
a pure function, and the padded path end to end on the CPU with the
kernel call replaced by a recorder that answers from the host library.
No kernel compiles here but in the one `kernel` + `slow` test at the
end, which runs the real fused kernel interpreted."""

import inspect
import time

import numpy as np
import pytest

import tendermint_tpu.ops.ed25519_tables as tbl_mod
import tendermint_tpu.services.verifier as vmod
from tendermint_tpu.crypto.keys import PubKey, gen_priv_key
from tendermint_tpu.services.verifier import (
    PLACEHOLDER_KEY,
    ShardedTableBatchVerifier,
    TableBatchVerifier,
    commit_launch_shape,
)
from tendermint_tpu.telemetry import launchlog
from tendermint_tpu.telemetry.launchlog import LAUNCHLOG



@pytest.fixture(autouse=True)
def _ledger_reset():
    LAUNCHLOG.clear()
    launchlog._tls.rec = None
    yield
    LAUNCHLOG.clear()
    launchlog._tls.rec = None


N_LAUNCH = {1: 128, 4: 128, 100: 128, 128: 128, 130: 256, 1000: 1024, 1024: 1024}
CHUNKS = {
    1: [(1, 1)],
    2: [(2, 16)],
    5: [(5, 16)],
    6: [(6, 16)],
    8: [(8, 16)],
    16: [(16, 16)],
    17: [(17, 32)],
    64: [(64, 64)],
    65: [(64, 64), (1, 16)],
}


class TestShapeRule:
    @pytest.mark.parametrize("k", sorted(CHUNKS))
    @pytest.mark.parametrize("n", sorted(N_LAUNCH))
    def test_launch_shape_and_chunks(self, n, k):
        n_launch, chunks = commit_launch_shape(k, n)
        assert n_launch == N_LAUNCH[n]
        assert chunks == CHUNKS[k]
        assert sum(real for real, _ in chunks) == k
        for real, k_launch in chunks:
            # every stack the rule gives is one the fused kernel tiles
            assert real <= k_launch
            assert k == 1 or tbl_mod._fused_tile_geometry(
                k_launch * n_launch, n_launch
            ) == (tbl_mod.V_TILE, k_launch)

    @pytest.mark.parametrize("n", sorted(N_LAUNCH))
    def test_every_fast_sync_window_is_one_shape(self, n):
        shapes = {
            (n_launch, k_launch)
            for k in range(2, 17)
            for n_launch, chunks in [commit_launch_shape(k, n)]
            for _, k_launch in chunks
        }
        assert shapes == {(N_LAUNCH[n], 16)}

    def test_a_walk_of_any_length_is_three_shapes(self):
        stacks = {
            k_launch
            for k in range(2, 400)
            for _, k_launch in commit_launch_shape(k, 100)[1]
        }
        assert stacks == {16, 32, 64}

    @pytest.mark.parametrize("cls", [TableBatchVerifier, ShardedTableBatchVerifier])
    def test_no_verifier_carries_a_rule_of_its_own(self, cls):
        """The tile and the stacks are named in `commit_launch_shape`
        alone: a verifier that grows its own copy of the rule again
        (`% 128`, `k >= 8`, `MAX_FUSED_STACK`) fails here."""
        own = "".join(
            inspect.getsource(f)
            for name, f in vars(cls).items()
            if name
            in ("prebuild", "_launch_keys", "launch_verify_commits", "_launch_mesh_tables")
        )
        for word in ("% 128", "V_TILE", "MAX_FUSED_STACK", "% 8", ">= 8"):
            assert word not in own, f"{cls.__name__} has its own {word!r}"
        assert "_window_shape(" in own
        # ... and `_window_shape` is the rule's one caller
        module = inspect.getsource(vmod)
        assert module.count("commit_launch_shape(") == 2  # its def, its call
        assert "commit_launch_shape(" in inspect.getsource(vmod._window_shape)


# -- the padded path, kernel replaced by a recorder ---------------------------


class _Tables:
    """Stands where the comb table would: the recorder reads the key of
    each launch column from it."""

    def __init__(self, keys):
        self.keys = keys
        self.nbytes = 0


class _Recorder:
    """`verify_tables_kernel`'s stand-in: answers each lane from the
    host library by the key of its column, and True for every lane
    whose rows are zero (absent, pad, refused by the precheck): what
    the real kernel says there is garbage the caller must mask."""

    def __init__(self, msg_by_sig):
        self.msg_by_sig = msg_by_sig
        self.shapes = []  # (n_launch, lanes) per call

    def __call__(self, tables, s, h, r):
        n_launch = len(tables.keys)
        self.shapes.append((n_launch, s.shape[0]))
        out = np.ones(s.shape[0], dtype=bool)
        for lane in range(s.shape[0]):
            sig = bytes(r[lane]) + bytes(s[lane])
            msg = self.msg_by_sig.get(sig)
            if msg is not None:
                out[lane] = PubKey(tables.keys[lane % n_launch]).verify(msg, sig)
        return out


def _set(n):
    privs = [gen_priv_key((i + 1).to_bytes(32, "little")) for i in range(n)]
    return privs, [p.pub_key.data for p in privs]


def _window(privs, k, salt=b"w"):
    commits = []
    for c in range(k):
        msgs = [b"%s-%d-%d" % (salt, c, i) for i in range(len(privs))]
        commits.append((msgs, [p.sign(m) for p, m in zip(privs, msgs)]))
    return commits


def _recorded(monkeypatch, verifier, commits, built=None):
    """Install the recorder and a table build that builds nothing."""
    rec = _Recorder(
        {sig: msg for msgs, sigs in commits for msg, sig in zip(msgs, sigs) if sig}
    )
    monkeypatch.setattr(tbl_mod, "verify_tables_kernel", rec)

    def build(keys):
        if built is not None:
            built.append(keys)
        return _Tables(keys), np.ones(len(keys), dtype=bool), "full", len(keys)

    monkeypatch.setattr(verifier, "_build_tables", build)
    return rec


def _wait_for_a_table(verifier):
    """`prebuild` builds on a thread of its own."""
    deadline = time.monotonic() + 5.0
    while not verifier._tables and time.monotonic() < deadline:
        time.sleep(0.01)


def _non_canonical(sig):
    """A signature whose S is S + L: the same point, not canonical."""
    from tendermint_tpu.ops.ed25519_kernel import L

    s = int.from_bytes(sig[32:], "little") + L
    return sig[:32] + s.to_bytes(32, "little")


FAULTS = ["absent", "forged", "non_canonical_s", "malformed_key"]


def _stack(k):
    """The stack a window of up to 16 commits is launched at."""
    return 1 if k == 1 else 16


class TestPaddedPath:
    @pytest.mark.parametrize("fault", FAULTS)
    @pytest.mark.parametrize("k", [1, 2, 6, 16])
    @pytest.mark.parametrize("n", [100, 130])
    def test_grid_equals_the_host_loop(self, monkeypatch, n, k, fault):
        """K = 1 is the materialized stack of one (reached here through
        `min_device_batch`, at 1,000 validators by every window of one
        commit), K = 2 and 16 the ends of the padded stack of 16."""
        privs, pubs = _set(n)
        commits = _window(privs, k)
        # first lane, last column, last commit
        at = sorted({(0, 0), (min(3, k - 1), n - 1), (k - 1, n // 2)})
        for c, i in at:
            msgs, sigs = commits[c]
            if fault == "absent":
                msgs[i] = sigs[i] = None
            elif fault == "forged":
                sigs[i] = bytes([sigs[i][0] ^ 4]) + sigs[i][1:]
            elif fault == "non_canonical_s":
                sigs[i] = _non_canonical(sigs[i])
        if fault == "malformed_key":
            pubs[n - 1] = pubs[n - 1][:31]
            pubs[7] = b""
        v = TableBatchVerifier(min_device_batch=1)
        want = v._host_commit_loop(pubs, commits)
        rec = _recorded(monkeypatch, v, commits)
        got = v.verify_commits(pubs, commits, force_fused=True)
        assert rec.shapes == [(N_LAUNCH[n], _stack(k) * N_LAUNCH[n])]
        assert got.shape == (k, n) and got.dtype == bool
        assert (got == want).all()
        assert int((~got).sum()) == (2 * k if fault == "malformed_key" else len(at))

    @pytest.mark.parametrize("k", [1, 2, 16])
    def test_no_pad_column_or_pad_commit_ever_reports_true(self, monkeypatch, k):
        """What the kernel says of a lane whose rows are zero is garbage.
        Here it says True of every lane it is handed: the verdicts are
        the real lanes' alone, and an absent vote among them stays False."""
        n = 130
        privs, pubs = _set(n)
        commits = _window(privs, k)
        commits[k - 1][0][n - 1] = commits[k - 1][1][n - 1] = None
        v = TableBatchVerifier(min_device_batch=1)
        _recorded(monkeypatch, v, commits)
        lanes = []
        monkeypatch.setattr(
            tbl_mod,
            "verify_tables_kernel",
            lambda tables, s, h, r: (lanes.append(s.shape[0]), np.ones(s.shape[0], dtype=bool))[1],
        )
        got = v.verify_commits(pubs, commits, force_fused=True)
        assert lanes == [_stack(k) * 256]
        assert got.shape == (k, n)
        assert int(got.sum()) == k * n - 1 and not got[k - 1, n - 1]

    def test_pad_lanes_are_not_walked_by_the_prep_loop(self, monkeypatch):
        privs, pubs = _set(100)
        commits = _window(privs, 6)
        v = TableBatchVerifier(min_device_batch=1)
        _recorded(monkeypatch, v, commits)
        seen = []
        real_prep = tbl_mod.prepare_commit_lanes
        monkeypatch.setattr(
            tbl_mod,
            "prepare_commit_lanes",
            lambda keys, part: (seen.append((len(keys), len(part))), real_prep(keys, part))[1],
        )
        assert v.verify_commits(pubs, commits, force_fused=True).all()
        assert seen == [(100, 6)]  # real lanes only: the pad is one zero array

    def test_a_long_walk_is_chunked_and_sliced(self, monkeypatch):
        privs, pubs = _set(4)
        commits = _window(privs, 81)
        commits[63][1][3] = bytes([commits[63][1][3][0] ^ 1]) + commits[63][1][3][1:]
        commits[80][0][0] = commits[80][1][0] = None
        v = TableBatchVerifier(min_device_batch=1)
        want = v._host_commit_loop(pubs, commits)
        rec = _recorded(monkeypatch, v, commits)
        got = v.verify_commits(pubs, commits, force_fused=True)
        assert rec.shapes == [(128, 64 * 128), (128, 32 * 128)]
        assert (got == want).all() and int((~got).sum()) == 2

    def test_off_the_chip_nothing_is_padded(self, monkeypatch):
        privs, pubs = _set(100)
        commits = _window(privs, 6)
        v = TableBatchVerifier(min_device_batch=1)
        rec = _recorded(monkeypatch, v, commits)
        assert v.verify_commits(pubs, commits).all()  # auto, CPU backend
        assert rec.shapes == [(100, 600)]

    def test_small_windows_stay_on_the_host_library_by_real_lanes(self, monkeypatch):
        """5 commits of 100 validators are 500 real lanes, under 512,
        though their padded launch would be 2,048."""
        privs, pubs = _set(100)
        commits = _window(privs, 5)
        v = TableBatchVerifier()  # DEVICE_MIN_BATCH
        rec = _recorded(monkeypatch, v, commits)
        assert v.verify_commits(pubs, commits, force_fused=True).all()
        assert rec.shapes == []

    def test_one_commit_keeps_its_stack_of_one(self, monkeypatch):
        privs, pubs = _set(130)
        commits = _window(privs, 1)
        v = TableBatchVerifier(min_device_batch=1)
        rec = _recorded(monkeypatch, v, commits)
        assert v.verify_commits(pubs, commits, force_fused=True).all()
        assert rec.shapes == [(256, 256)]

    def test_prebuild_and_every_window_size_share_one_table(self, monkeypatch):
        import jax

        privs, pubs = _set(100)
        v = TableBatchVerifier(min_device_batch=1)
        built = []
        _recorded(monkeypatch, v, _window(privs, 1), built)
        # prebuild asks the backend whether windows are shaped for the
        # fused kernel: steer it to the TPU's answer
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        v.prebuild(pubs)
        _wait_for_a_table(v)
        assert len(built) == 1 and len(built[0]) == 128
        assert built[0][:100] == tuple(pubs)
        assert set(built[0][100:]) == {PLACEHOLDER_KEY}
        for k in (1, 2, 6, 16, 17, 70):
            commits = _window(privs, k, salt=b"k%d" % k)
            _recorded(monkeypatch, v, commits, built)
            assert v.verify_commits(pubs, commits).all()
        assert len(built) == 1 and len(v._tables) == 1

    @pytest.mark.parametrize(
        "n,k", [(100, 6), (100, 1), (100, 2), (100, 16), (130, 1), (130, 2), (130, 16)]
    )
    def test_ledger_record_carries_the_launch_shape(self, monkeypatch, n, k):
        privs, pubs = _set(n)
        commits = _window(privs, k)
        v = TableBatchVerifier(min_device_batch=1)
        _recorded(monkeypatch, v, commits)
        v.verify_commits(pubs, commits, force_fused=True)
        rec = LAUNCHLOG.recent(kind="tables")[-1]
        assert (rec["k_launch"], rec["n_launch"]) == (_stack(k), N_LAUNCH[n])
        assert rec["rows"] == k * n
        # at the stack of one as at the stack of 16, what was launched
        # is what was asked, what the cache kept back and the padding
        assert (
            rec["rows"] + rec.get("rows_cached", 0) + rec["rows_padded"]
            == rec["k_launch"] * rec["n_launch"]
        )
        # off the fused path the shape is the window's own
        v.verify_commits(pubs, commits)
        rec = LAUNCHLOG.recent(kind="tables")[-1]
        assert (rec["k_launch"], rec["n_launch"], rec["rows_padded"]) == (k, n, 0)

    def test_a_forged_commit_is_refused_by_validator_entry_and_height(self, monkeypatch):
        from tendermint_tpu.types import Commit
        from tendermint_tpu.types.errors import ValidationError

        valset, entries = _signed_window(100, range(5, 11))
        bid, height, commit = entries[4]
        votes = list(commit.precommits)
        sig = votes[99].signature
        votes[99] = votes[99].with_signature(bytes([sig[0] ^ 1]) + sig[1:])
        forged = entries[:4] + [(bid, height, Commit(block_id=bid, precommits=votes))] + entries[5:]

        def refusal(verifier, window):
            with pytest.raises(ValidationError) as e:
                valset.verify_commit_batched(CHAIN, window, verifier)
            return str(e.value)

        v, rec = _padded_verifier(monkeypatch, forged)
        host = vmod.HostBatchVerifier()
        got = refusal(v, forged)
        assert rec.shapes == [(128, 2048)]
        assert got == refusal(host, forged)
        assert "validator 99 (batch entry 4, height 9)" in got
        valset.verify_commit_batched(CHAIN, entries, v)  # the clean window passes

    @pytest.mark.parametrize("k", [1, 2, 16])
    def test_the_quorum_edge_of_uniform_power_is_a_count(self, monkeypatch, k):
        """3m validators of one power: more than 2/3 of the power is
        2m + 1 precommits. A window whose last commit holds 2m valid
        ones is refused and one with 2m + 1 accepted, through the padded
        table path as by `verify_commit` on the host; the 29 pad columns
        and the pad commits weigh nothing on either side of the edge."""
        from tendermint_tpu.types import Commit
        from tendermint_tpu.types.errors import ValidationError

        m = 33
        valset, entries = _signed_window(3 * m, range(5, 5 + k))
        assert {val.voting_power for val in valset.validators} == {10}
        bid, height, commit = entries[-1]

        def holding(signed):
            votes = [vote if i < signed else None for i, vote in enumerate(commit.precommits)]
            return entries[:-1] + [(bid, height, Commit(block_id=bid, precommits=votes))]

        v, rec = _padded_verifier(monkeypatch, entries)
        host = vmod.HostBatchVerifier()
        short, enough = holding(2 * m), holding(2 * m + 1)
        for verifier in (v, host):
            with pytest.raises(ValidationError, match=f"insufficient voting power: {20 * m} of {30 * m}"):
                valset.verify_commit_batched(CHAIN, short, verifier)
            valset.verify_commit_batched(CHAIN, enough, verifier)
        with pytest.raises(ValidationError, match="insufficient"):
            valset.verify_commit(CHAIN, bid, height, short[-1][2], host)
        valset.verify_commit(CHAIN, bid, height, enough[-1][2], host)
        assert rec.shapes == [(128, _stack(k) * 128)] * 2


CHAIN = "launch-shape"


def _signed_window(n, heights):
    """A genesis set of `n` validators of power 10 and one fully signed
    commit per height: (valset, [(block_id, height, commit), ...])."""
    from tendermint_tpu.testing.nemesis import make_genesis
    from tendermint_tpu.types import BlockID, Commit
    from tendermint_tpu.types.part_set import PartSetHeader
    from tendermint_tpu.types.vote import VOTE_TYPE_PRECOMMIT, Vote

    genesis, privs = make_genesis(n, chain_id=CHAIN)
    entries = []
    for height in heights:
        bid = BlockID(bytes([height]) * 20, PartSetHeader(total=1, hash=b"\x22" * 20))
        votes = [
            p.sign_vote(
                CHAIN,
                Vote(
                    validator_address=p.address, validator_index=i, height=height,
                    round=0, timestamp=1, type=VOTE_TYPE_PRECOMMIT, block_id=bid,
                ),
            )
            for i, p in enumerate(privs)
        ]
        entries.append((bid, height, Commit(block_id=bid, precommits=votes)))
    return genesis.validator_set(), entries


def _padded_verifier(monkeypatch, entries):
    """A table verifier that launches every window at the padded shape,
    its kernel the recorder over the votes of `entries`."""

    class Padded(TableBatchVerifier):
        accepts_consumer = False

        def verify_commits(self, pubkeys, commits, force_fused=None):
            return super().verify_commits(pubkeys, commits, force_fused=True)

    v = Padded(min_device_batch=1)
    votes = [[vote for vote in c.precommits if vote is not None] for _b, _h, c in entries]
    rec = _recorded(
        monkeypatch,
        v,
        [([vote.sign_bytes(CHAIN) for vote in vs], [vote.signature for vote in vs]) for vs in votes],
    )
    return v, rec


class TestShardedPaddedPath:
    def _verifier(self, monkeypatch, n):
        from tendermint_tpu.parallel.mesh import MeshManager

        privs, pubs = _set(n)
        mgr = MeshManager(executor="device", reprobe_s=60.0)
        v = ShardedTableBatchVerifier(mesh=mgr, min_device_batch=1)
        calls = []

        def step(tables, s, h, r, lane_ok, power):
            calls.append((len(tables.keys), s.shape[0]))
            return np.asarray(lane_ok).copy(), 0

        monkeypatch.setattr(mgr, "tables_step", lambda: step)
        monkeypatch.setattr(
            v,
            "_tables_for_mesh",
            lambda keys, m: (_Tables(keys), np.ones(len(keys), dtype=bool)),
        )
        return privs, pubs, v, mgr, calls

    def test_the_sharded_verifier_asks_the_one_helper_per_chip(self, monkeypatch):
        privs, pubs, v, mgr, calls = self._verifier(monkeypatch, 16)
        asked = []
        real = vmod.commit_launch_shape
        monkeypatch.setattr(
            vmod,
            "commit_launch_shape",
            lambda k, n: (asked.append((k, n)), real(k, n))[1],
        )
        commits = _window(privs, 3)
        commits[1][0][5] = commits[1][1][5] = None
        got = v.verify_commits(pubs, commits, force_fused=True)
        ndev = mgr.n_active
        assert ndev == 8
        assert (3, 16 // ndev) in asked
        # 2 validators a chip pad to the 128 tile, 3 commits to 16
        assert calls == [(128 * ndev, 16 * 128 * ndev)]
        want = np.ones((3, 16), dtype=bool)
        want[1, 5] = False
        assert (got == want).all()
        rec = LAUNCHLOG.recent(kind="tables")[-1]
        assert (rec["k_launch"], rec["n_launch"]) == (16, 128 * ndev)
        assert rec["rows_padded"] == 16 * 128 * ndev - 48

    def test_sharded_prebuild_pads_to_the_mesh_launch_width(self, monkeypatch):
        import jax

        privs, pubs, v, mgr, calls = self._verifier(monkeypatch, 16)
        built = []
        monkeypatch.setattr(
            v,
            "_build_tables",
            lambda keys: (built.append(keys), (_Tables(keys), np.ones(len(keys), dtype=bool), "full", len(keys)))[1],
        )
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        pubs_at_launch = v._launch_keys(pubs, True, v._launch_chips(16))[0]
        v.prebuild(pubs)
        _wait_for_a_table(v)
        assert built == [pubs_at_launch] and len(pubs_at_launch) == 128 * 8
        assert v._launch_chips(10) == 1  # an uneven set is served by one chip


@pytest.mark.kernel
@pytest.mark.slow
def test_fused_kernel_interpreted_at_100_validators_matches_the_host():
    """The real fused kernel (interpreted off the TPU) over a table
    whose 28 pad columns the verifier's own build made: the only guard
    of the pad columns' table entries outside a chip run."""
    privs, pubs = _set(100)
    commits = _window(privs, 6)
    commits[0][1][0] = bytes([commits[0][1][0][0] ^ 2]) + commits[0][1][0][1:]
    commits[5][1][99] = _non_canonical(commits[5][1][99])
    commits[2][0][50] = commits[2][1][50] = None
    v = TableBatchVerifier(min_device_batch=1)
    want = v._host_commit_loop(pubs, commits)
    kernel = tbl_mod.verify_tables_kernel
    seen = []

    def fused(tables, s, h, r):
        seen.append((tables.shape[3], s.shape[0]))
        return kernel(tables, s, h, r, impl="fused")

    tbl_mod.verify_tables_kernel = fused
    try:
        got = v.verify_commits(pubs, commits, force_fused=True)
    finally:
        tbl_mod.verify_tables_kernel = kernel
    assert seen == [(128, 2048)]
    assert (got == want).all() and int((~got).sum()) == 3
