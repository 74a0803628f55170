"""BatchVerifier / TreeHasher service layer + mesh-sharded verification."""

import numpy as np
import pytest

from tendermint_tpu.crypto.keys import gen_priv_key
from tendermint_tpu.merkle.simple import (
    simple_hash_from_byte_slices,
    simple_hash_from_hashes,
)
from tendermint_tpu.services import (
    DeviceBatchVerifier,
    HostBatchVerifier,
    TreeHasher,
)


def _triples(n, corrupt=()):
    privs = [gen_priv_key(bytes([i + 1]) * 32) for i in range(n)]
    msgs = [b"msg-%d" % i for i in range(n)]
    out = []
    for i, (p, m) in enumerate(zip(privs, msgs)):
        sig = p.sign(m)
        if i in corrupt:
            sig = sig[:8] + bytes([sig[8] ^ 1]) + sig[9:]
        out.append((p.pub_key.data, m, sig))
    return out


class TestBatchVerifier:
    @pytest.mark.parametrize("cls", [HostBatchVerifier, DeviceBatchVerifier])
    def test_verify_batch_localizes_failures(self, cls):
        # min_device_batch=1 keeps DeviceBatchVerifier on the kernel path
        # (the default threshold would silently route to the host)
        v = cls() if cls is HostBatchVerifier else cls(min_device_batch=1)
        verdict = v.verify_batch(_triples(6, corrupt={1, 4}))
        assert verdict.tolist() == [True, False, True, True, False, True]

    def test_accumulate_flush(self):
        v = DeviceBatchVerifier(min_device_batch=1)
        triples = _triples(5, corrupt={2})
        idxs = [v.add(*t) for t in triples]
        assert idxs == [0, 1, 2, 3, 4]
        assert v.pending() == 5
        verdict = v.flush()
        assert verdict.tolist() == [True, True, False, True, True]
        assert v.pending() == 0
        assert v.flush().shape == (0,)

    def test_verify_one(self):
        v = HostBatchVerifier()
        (pk, m, sig) = _triples(1)[0]
        assert v.verify_one(pk, m, sig)
        assert not v.verify_one(pk, m + b"!", sig)

    def test_host_device_agree(self):
        triples = _triples(9, corrupt={0, 8})
        host = HostBatchVerifier().verify_batch(triples)
        dev = DeviceBatchVerifier(min_device_batch=1).verify_batch(triples)
        assert (host == dev).all()


class TestTreeHasher:
    def test_device_root_matches_host(self):
        items = [b"item-%d" % i for i in range(13)]
        assert TreeHasher("device", min_device_leaves=2).root_from_items(items) == simple_hash_from_byte_slices(items)

    def test_root_from_hashes(self):
        from tendermint_tpu.merkle.simple import leaf_hash

        hashes = [leaf_hash(b"x%d" % i) for i in range(7)]
        assert TreeHasher("device", min_device_leaves=2).root_from_hashes(hashes) == simple_hash_from_hashes(hashes)
        assert TreeHasher("host").root_from_hashes(hashes) == simple_hash_from_hashes(hashes)

    def test_ripemd_device_tree_matches_host(self):
        # the reference's bit-compat tree variant now runs on device too
        th = TreeHasher("device", algo="ripemd160", min_device_leaves=2)
        items = [b"item-%d" % i for i in range(11)]
        assert th.root_from_items(items) == simple_hash_from_byte_slices(items, "ripemd160")
        # already-hashed aggregation rides the device tree too
        from tendermint_tpu.merkle.simple import leaf_hash

        hashes = [leaf_hash(b"h%d" % i, "ripemd160") for i in range(5)]
        assert th.root_from_hashes(hashes) == simple_hash_from_hashes(hashes, "ripemd160")

    def test_edge_counts(self):
        th = TreeHasher("device", min_device_leaves=2)
        assert th.root_from_items([]) == b""
        assert th.root_from_items([b"one"]) == simple_hash_from_byte_slices([b"one"])


class TestIncrementalTableBuild:
    def test_valset_diff_rebuilds_only_changed_columns(self, monkeypatch):
        """Swapping 1 validator of 8 must build tables for exactly the
        1 new key (unchanged columns gathered from the cached set) and
        verify correctly right away (VERDICT r3 #3; EndBlock diffs touch
        few keys, reference state/execution.go:120-159)."""
        import tendermint_tpu.services.verifier as svc
        from tendermint_tpu.ops import ed25519_tables as tb
        from tendermint_tpu.services import TableBatchVerifier

        built_counts: list[int] = []
        _orig_host = tb.host_build_key_tables

        def counting_host_build(pubs):
            built_counts.append(len(pubs))
            return _orig_host([bytes(pk) for pk in pubs])

        # full builds route through the (device) build_key_tables; back
        # both builders with the host builder to keep the test
        # device-free while counting how many keys get built
        monkeypatch.setattr(
            tb, "build_key_tables", lambda arr: counting_host_build(list(arr))
        )
        monkeypatch.setattr(tb, "host_build_key_tables", counting_host_build)
        assert svc is not None  # imported for monkeypatch targets

        n = 8
        privs = [gen_priv_key(bytes([i + 1]) * 32) for i in range(n)]
        pubs = [p.pub_key.data for p in privs]
        v = TableBatchVerifier(min_device_batch=1)

        def commit_for(privs_, pubs_):
            msgs = [b"vote-%d" % i for i in range(len(privs_))]
            sigs = [p.sign(m) for p, m in zip(privs_, msgs)]
            return v.verify_commits(pubs_, [(msgs, sigs)])

        out = commit_for(privs, pubs)
        assert out.all()
        assert built_counts == [n]  # full build of all 8

        # rotate validator 3 out, a brand-new key in
        new_priv = gen_priv_key(b"\x99" * 32)
        privs2 = list(privs)
        privs2[3] = new_priv
        pubs2 = [p.pub_key.data for p in privs2]
        out2 = commit_for(privs2, pubs2)
        assert out2.all()
        assert built_counts == [n, 1]  # incremental: only the new key

        # the incremental tables are bit-identical to a from-scratch build
        inc_tables, inc_ok = v._tables_for(tuple(pubs2))
        full_tables, full_ok = _orig_host(pubs2)
        np.testing.assert_array_equal(np.asarray(inc_tables), full_tables)
        assert inc_ok.tolist() == full_ok.tolist()

    def test_prebuild_warms_cache_async(self, monkeypatch):
        from tendermint_tpu.ops import ed25519_tables as tb
        from tendermint_tpu.services import TableBatchVerifier

        _orig_host = tb.host_build_key_tables
        monkeypatch.setattr(
            tb,
            "build_key_tables",
            lambda arr: _orig_host([bytes(pk) for pk in arr]),
        )
        privs = [gen_priv_key(bytes([i + 1]) * 32) for i in range(4)]
        pubs = [p.pub_key.data for p in privs]
        v = TableBatchVerifier(min_device_batch=1)
        v.prebuild(pubs)
        import time

        deadline = time.time() + 30
        key = v._cache_key(tuple(pubs))
        while time.time() < deadline and key not in v._tables:
            time.sleep(0.05)
        assert key in v._tables


class TestShardedVerify:
    def test_verify_and_tally_on_8_device_mesh(self):
        import jax

        from tendermint_tpu.ops.ed25519_kernel import prepare_batch
        from tendermint_tpu.parallel.mesh import (
            batch_mesh,
            pad_to_multiple,
            sharded_verify_and_tally,
        )

        assert len(jax.devices()) == 8, "conftest must force the 8-device cpu mesh"
        triples = _triples(10, corrupt={3})
        pubs, msgs, sigs = (list(x) for x in zip(*triples))
        pub, r, s, h, pre = prepare_batch(pubs, msgs, sigs)
        powers = np.full(10, 5, dtype=np.int32)
        arrs, powers, valid = pad_to_multiple([pub, r, s, h], powers, 8)
        step = sharded_verify_and_tally(batch_mesh())
        ok, total = step(*arrs, powers)
        ok = np.asarray(ok)[:valid]
        assert ok.tolist() == [True] * 3 + [False] + [True] * 6
        assert int(total) == 45  # 9 valid * power 5

    def test_distributed_seam_single_process(self):
        """The multi-host seam (parallel/distributed.py) must compose
        with the sharded verify step degenerately on one process: same
        initialize/global-mesh/host_local_to_global calls a multi-host
        deployment makes (SURVEY §5.8)."""
        import jax
        from jax.sharding import PartitionSpec as P

        from tendermint_tpu.ops.ed25519_kernel import prepare_batch
        from tendermint_tpu.parallel import distributed as dist
        from tendermint_tpu.parallel.mesh import (
            BATCH_AXIS,
            pad_to_multiple,
            sharded_verify_and_tally,
        )

        dist.initialize()  # single-process no-op
        assert dist.process_info() == (0, 1)
        mesh = dist.global_batch_mesh()
        assert mesh.devices.size == 8

        triples = _triples(8, corrupt={2})
        pubs, msgs, sigs = (list(x) for x in zip(*triples))
        pub, r, s, h, _pre = prepare_batch(pubs, msgs, sigs)
        powers = np.full(8, 2, dtype=np.int32)
        arrs, powers, valid = pad_to_multiple([pub, r, s, h], powers, 8)
        spec = P(BATCH_AXIS)
        placed = [dist.host_local_to_global(mesh, spec, a) for a in arrs]
        pw = dist.host_local_to_global(mesh, spec, powers)
        ok, total = sharded_verify_and_tally(mesh)(*placed, pw)
        ok = np.asarray(ok)[:valid]
        assert ok.tolist() == [True, True, False, True, True, True, True, True]
        assert int(total) == 2 * 7

    def test_tables_path_on_8_device_mesh(self):
        """The production TABLE fast path sharded along the validator
        axis: each device holds 1/8 of the comb-table columns and the
        lanes of its own validators; a planted bad signature must
        localize and the psum power tally must exclude it."""
        import jax

        from tendermint_tpu.ops.ed25519_tables import (
            host_build_key_tables,
            prepare_commit_lanes,
        )
        from tendermint_tpu.parallel.mesh import (
            batch_mesh,
            shard_lanes_validator_major,
            sharded_tables_verify_and_tally,
            unshard_lanes_validator_major,
        )

        assert len(jax.devices()) == 8
        n_vals, k = 16, 2
        privs = [gen_priv_key(bytes([i + 1]) * 32) for i in range(n_vals)]
        pubs = [p.pub_key.data for p in privs]
        commits = []
        for c in range(k):
            msgs = [b"commit-%d-val-%d" % (c, i) for i in range(n_vals)]
            sigs = [p.sign(m) for p, m in zip(privs, msgs)]
            commits.append((msgs, sigs))
        # plant a bad signature: commit 1, validator 5
        msgs1, sigs1 = commits[1]
        sigs1[5] = sigs1[5][:10] + bytes([sigs1[5][10] ^ 1]) + sigs1[5][11:]

        tables, key_ok = host_build_key_tables(pubs)
        assert key_ok.all()
        s, h, r, pre = prepare_commit_lanes(pubs, commits)
        assert pre.all()
        lane_ok = pre & np.tile(key_ok, k)
        # non-uniform powers: proves lane/power alignment survives the
        # shard-major reorder (uniform powers would mask a mispairing)
        powers = (1 + np.arange(k * n_vals, dtype=np.int32)) % 7 + 1
        s, h, r, lane_ok, powers = shard_lanes_validator_major(
            [s, h, r, lane_ok, powers], n_vals, 8
        )

        step = sharded_tables_verify_and_tally(batch_mesh())
        ok, total = step(tables, s, h, r, lane_ok, powers)
        ok = unshard_lanes_validator_major(np.asarray(ok), n_vals, 8)
        expect = np.ones(k * n_vals, dtype=bool)
        expect[1 * n_vals + 5] = False
        assert ok.tolist() == expect.tolist()
        powers_cm = unshard_lanes_validator_major(powers, n_vals, 8)
        assert int(total) == int(powers_cm[expect].sum())


class TestFusedPathShaping:
    """Always-on gate for TableBatchVerifier.verify_commits' chunk/pad
    logic (VERDICT r4 weak #7): K not a multiple of 8, padded absent-vote
    tails, bad signatures adjacent to the pad, and chunking across
    MAX_FUSED_STACK — all on the CPU mesh via force_fused, independent of
    the kernel-marked pallas suites."""

    def _verifier_with_tables(self, n):
        import jax.numpy as jnp

        from tendermint_tpu.ops.ed25519_tables import host_build_key_tables
        from tendermint_tpu.services import TableBatchVerifier

        privs = [gen_priv_key(bytes([i + 1]) * 32) for i in range(n)]
        pubs = tuple(p.pub_key.data for p in privs)
        v = TableBatchVerifier(min_device_batch=1)
        tables, ok = host_build_key_tables(list(pubs))
        v._tables[v._cache_key(pubs)] = (pubs, jnp.asarray(tables), ok)
        return privs, pubs, v

    def _commits(self, privs, k, corrupt=(), absent=()):
        n = len(privs)
        expected = np.zeros((k, n), dtype=bool)
        commits = []
        for ci in range(k):
            msgs, sigs = [], []
            for vi, p in enumerate(privs):
                if (ci, vi) in absent:
                    msgs.append(None)
                    sigs.append(None)
                    continue
                m = b"commit-%d-vote-%d" % (ci, vi)
                s = p.sign(m)
                if (ci, vi) in corrupt:
                    s = s[:4] + bytes([s[4] ^ 1]) + s[5:]
                else:
                    expected[ci, vi] = True
                msgs.append(m)
                sigs.append(s)
            commits.append((msgs, sigs))
        return commits, expected

    def test_pad_and_chunk_boundaries(self):
        """The real kernel (XLA scan on the CPU) at the padded launch
        shape: 8 validators in a 128-column table whose pad columns the
        verifier's own incremental build made, 13 commits in a stack of
        16 (`commit_launch_shape`; chunking past 64 commits is gated
        without a compile in tests/test_launch_shape.py)."""
        import tendermint_tpu.ops.ed25519_tables as tbl_mod

        seen = []
        real_kernel = tbl_mod.verify_tables_kernel

        def spy(tables, s, h, r):
            seen.append((tables.shape[3], s.shape[0]))
            return real_kernel(tables, s, h, r)

        privs, pubs, v = self._verifier_with_tables(8)
        # bad sigs in the first commit, mid-stack, and in the LAST REAL
        # commit right against the padded tail (ci=12), in the last real
        # column right against the pad columns (vi=7); absent votes too
        commits, expected = self._commits(
            privs,
            13,
            corrupt={(0, 0), (7, 7), (12, 3)},
            absent={(2, 5), (12, 7)},
        )
        tbl_mod.verify_tables_kernel = spy
        try:
            got = v.verify_commits(pubs, commits, force_fused=True)
        finally:
            tbl_mod.verify_tables_kernel = real_kernel
        assert got.shape == (13, 8)
        assert (got == expected).all()
        assert seen == [(128, 16 * 128)]  # N 8 -> 128, K 13 -> 16

    def _spy_prep_fake_kernel(self, monkeypatch):
        """Record prepare_commit_lanes part sizes and replace the device
        kernel with all-True lanes — these tests assert SHAPING decisions
        (pad/no-pad) and mask plumbing, not curve math (covered above and
        in the kernel tier), so skip the XLA compile."""
        import tendermint_tpu.ops.ed25519_tables as tbl_mod

        seen = []
        real_prep = tbl_mod.prepare_commit_lanes
        monkeypatch.setattr(
            tbl_mod,
            "prepare_commit_lanes",
            lambda pubs, part: (seen.append(len(part)), real_prep(pubs, part))[1],
        )
        monkeypatch.setattr(
            tbl_mod,
            "verify_tables_kernel",
            lambda tables, s, h, r: np.ones(s.shape[0], dtype=bool),
        )
        return seen

    def test_unfusable_shape_takes_single_launch(self, monkeypatch):
        seen = self._spy_prep_fake_kernel(monkeypatch)
        privs, pubs, v = self._verifier_with_tables(5)
        commits, presence = self._commits(privs, 3, absent={(1, 4), (2, 0)})
        got = v.verify_commits(pubs, commits)  # auto: cpu backend, no pad
        assert (got == presence).all()  # absent lanes masked by precheck
        assert seen == [3]  # K stays unpadded off the fused path

    def test_k1_commit_never_padded_on_cpu(self, monkeypatch):
        """ADVICE r4 (medium): the consensus-loop K=1 commit must not be
        shaped for the fused kernel when fused can't or shouldn't run."""
        seen = self._spy_prep_fake_kernel(monkeypatch)
        privs, pubs, v = self._verifier_with_tables(8)
        commits, presence = self._commits(privs, 1, absent={(0, 6)})
        got = v.verify_commits(pubs, commits)
        assert (got == presence).all()
        assert seen == [1]


class TestBulkTurnover:
    def test_large_diff_routes_to_device_build(self, monkeypatch):
        """A valset rotation larger than MAX_INCREMENTAL_KEYS must still
        build incrementally — missing keys as ONE device build call, not
        a host per-key loop or a full rebuild (VERDICT r4 item 4; the
        500-key bench shape, scaled for the CPU tier)."""
        import jax.numpy as jnp

        import tendermint_tpu.services.verifier as vmod
        from tendermint_tpu.ops.ed25519_tables import host_build_key_tables
        from tendermint_tpu.services import TableBatchVerifier

        privs = [gen_priv_key(bytes([i + 1]) * 32) for i in range(16)]
        pubs = tuple(p.pub_key.data for p in privs)
        v = TableBatchVerifier(min_device_batch=1)
        tables, ok = host_build_key_tables(list(pubs))
        v._tables[v._cache_key(pubs)] = (pubs, jnp.asarray(tables), ok)
        v.MAX_INCREMENTAL_KEYS = 4  # scale the 128-key threshold down

        device_builds = []
        import tendermint_tpu.ops.ed25519_tables as tbl_mod

        def fake_device_build(pub_arr, chunk=2048):
            # chunk-shape padding happens INSIDE build_key_tables (one
            # executable for all TPU builds), so the seam receives the
            # raw missing keys
            device_builds.append(pub_arr.shape[0])
            t, okk = host_build_key_tables([bytes(row) for row in pub_arr])
            return jnp.asarray(t), okk

        monkeypatch.setattr(tbl_mod, "build_key_tables", fake_device_build)

        # rotate 8 of 16 keys (> the scaled threshold)
        new_privs = [gen_priv_key(bytes([100 + i]) * 32) for i in range(8)]
        pubs2 = list(pubs)
        for i, np_ in enumerate(new_privs):
            pubs2[i * 2] = np_.pub_key.data
        t2, ok2 = v._tables_for(tuple(pubs2))
        assert device_builds == [8], device_builds  # one bulk device build
        assert ok2.all()

        # the assembled tables must actually verify a commit of the new set
        all_privs = {p.pub_key.data: p for p in privs + new_privs}
        msgs = [b"turnover-%d" % i for i in range(16)]
        sigs = [all_privs[pk].sign(m) for pk, m in zip(pubs2, msgs)]
        got = v.verify_commits(pubs2, [(msgs, sigs)])
        assert got.shape == (1, 16) and got.all()
