"""A validator set that changes, at the TPU's launch shape, on the CPU.

On the TPU `TableBatchVerifier` pads a set to its launch width with
`PLACEHOLDER_KEY` (16 keys -> 128 columns here, 1,000 -> 1,024 on the
chip), so a cached table is wider than its set has distinct keys. The
CPU never pads on its own: every test here forces the padded shape
(`force_fused=True`, or `_fused` patched) and patches the device's
table builder to the host build, so nothing but the plain XLA verify
kernel of one shape compiles. Every verdict is held, lane by lane, to
the plain reference: the host ed25519 library over the same seeded
keys, messages and signatures.
"""

import threading
import time

import numpy as np
import pytest

import tendermint_tpu.ops.ed25519_tables as tbl_mod
from tendermint_tpu.crypto.keys import PubKey, gen_priv_key
from tendermint_tpu.services.verifier import (
    PLACEHOLDER_KEY,
    ShardedTableBatchVerifier,
    TableBatchVerifier,
    TableBuildError,
)
from tendermint_tpu.telemetry import REGISTRY, TRACER
from tendermint_tpu.telemetry.launchlog import LAUNCHLOG

from tests.test_fastsync import _pipelined_reactor, wait_until

WAIT_S = 30.0  # every wait here ends by itself: a join, an event, a sync

PRIVS = [gen_priv_key(b"valset-change-%03d" % i + bytes(15)) for i in range(130)]
PUBS = [p.pub_key.data for p in PRIVS]
G = list(range(16))  # the genesis set's key ranks; 16.. are standby keys
MALFORMED = -1  # a rank that stands for a 31-byte key


HOST_BUILD = tbl_mod.host_build_key_tables
BUILT: dict[bytes, tuple] = {}  # a key's column, computed once a process


@pytest.fixture(autouse=True)
def host_built_tables(monkeypatch):
    """The device's table builder answered by the host's: no build
    kernel compiles."""

    def host_build(keys):
        for pk in {bytes(k) for k in keys} - set(BUILT):
            t, ok = HOST_BUILD([pk])
            BUILT[pk] = (t, bool(ok[0]))
        cols = [BUILT[bytes(k)] for k in keys]
        return np.concatenate([t for t, _ in cols], axis=3), np.array([ok for _, ok in cols])

    monkeypatch.setattr(tbl_mod, "host_build_key_tables", host_build)
    monkeypatch.setattr(
        tbl_mod, "build_key_tables", lambda pub: host_build([bytes(k) for k in np.asarray(pub)])
    )


def keys_of(ranks):
    return [PUBS[r][:31] if r == MALFORMED else PUBS[r] for r in ranks]


def commit_of(ranks, salt: bytes, forged=()):
    """One commit over the set: every lane a sound signature of its key,
    but the `forged` lanes, where one bit of the signature is flipped."""
    msgs = [b"%s/%d" % (salt, i) for i in range(len(ranks))]
    sigs = [PRIVS[max(r, 0)].sign(m) for r, m in zip(ranks, msgs)]
    for i in forged:
        sigs[i] = bytes([sigs[i][0] ^ 4]) + sigs[i][1:]
    return msgs, sigs


def reference(ranks, commit) -> np.ndarray:
    """The host library, lane by lane; a malformed key verifies nothing."""
    msgs, sigs = commit
    return np.array(
        [r != MALFORMED and PubKey(PUBS[r]).verify(m, s) for r, m, s in zip(ranks, msgs, sigs)]
    )


def counter(name, **labels) -> float:
    return REGISTRY.counter_value(name, **labels)


EVENTS = ("hit", "miss", "joined", "incremental", "host_build")


def table_events() -> dict:
    return {e: counter("tendermint_verify_table_cache_total", event=e) for e in EVENTS}


def keys_built() -> dict:
    return {h: counter("tendermint_verify_table_keys_built_total", how=h) for h in ("host", "device")}


def rise(before: dict, after: dict) -> dict:
    return {k: int(after[k] - before[k]) for k in after if after[k] != before[k]}


def spans_since(name: str, t0: float) -> list[dict]:
    return [s for s in TRACER.recent(prefix=name) if s["start"] >= t0]


def column_keys(tables) -> list[bytes]:
    """Which key's table each column of a real comb table holds, read
    off the table itself: window 0, digit 1 is the key's own point.
    (Every column here was built by `host_built_tables`.)"""
    key_of_point = {t[0, 1, :, 0].tobytes(): pk for pk, (t, _ok) in BUILT.items()}
    first = np.asarray(tables[0, 1])  # (limbs, columns)
    return [key_of_point[first[:, c].tobytes()] for c in range(first.shape[1])]


# -- (a) set transitions, each through the real kernel at 128 columns ------------

JOIN_1 = G[:5] + [16] + G[5:]
JOIN_2 = G[:3] + [17] + G[3:9] + [16] + G[9:]
BUILT_1 = ({"miss": 1, "incremental": 1}, {"host": 1})
TRANSITIONS = {
    # name: (the sets in order, as key ranks in lane order; the cache events and the keys built the LAST set costs)
    "join_1": ([G, JOIN_1], BUILT_1),
    "join_2_at_once": ([G, JOIN_2], ({"miss": 1, "incremental": 1}, {"host": 2})),
    "leave_2": ([JOIN_2, G], ({"miss": 1, "incremental": 1}, {})),
    "power_change_only": ([JOIN_1, JOIN_1], ({"hit": 1}, {})),
    "joined_key_sorts_first": ([G, [16] + G], BUILT_1),
    "joined_key_sorts_last": ([G, G + [16]], BUILT_1),
    "malformed_key_in_the_set": ([G[:7] + [MALFORMED] + G[8:], G[:7] + [MALFORMED] + G[8:] + [16]], BUILT_1),
    "two_joins_in_a_row": ([G, JOIN_1, JOIN_1[:13] + [17] + JOIN_1[13:]], BUILT_1),
}


@pytest.mark.parametrize("case", sorted(TRANSITIONS))
def test_a_set_transition_at_the_padded_shape_verifies_as_the_host_library(case):
    """Every sound lane verifies, and a bad signature planted in each
    joined key's lane (and one old key's) is refused: the joined column
    is that key's table, not a pad column's and not a lenient one."""
    sets, last_cost = TRANSITIONS[case]
    v = TableBatchVerifier(min_device_batch=1)
    seen: set = set()
    for step, ranks in enumerate(sets):
        joined = [i for i, r in enumerate(ranks) if r not in seen and r != MALFORMED] if step else []
        seen = set(ranks)
        before_events, before_keys = table_events(), keys_built()
        sound = commit_of(ranks, b"%s-%d" % (case.encode(), step))
        got = v.verify_commits(keys_of(ranks), [sound], force_fused=True)
        cost = rise(before_events, table_events()), rise(before_keys, keys_built())
        want = reference(ranks, sound)
        assert got.shape == (1, len(ranks)) and (got[0] == want).all(), np.where(got[0] != want)[0]
        assert want.sum() == sum(r != MALFORMED for r in ranks)  # all but a malformed key's lane
        forged_at = sorted({*joined[:2], len(ranks) // 2} - {i for i, r in enumerate(ranks) if r == MALFORMED})
        bad = commit_of(ranks, b"%s-%d-forged" % (case.encode(), step), forged=forged_at)
        got = v.verify_commits(keys_of(ranks), [bad], force_fused=True)
        want = reference(ranks, bad)
        assert (got[0] == want).all() and not want[forged_at].any()
    assert cost == last_cost
    # the table the last launch used: each column holds its own key's table
    keys, _ = v._launch_keys(keys_of(sets[-1]), True)
    tables, ok = v._tables[v._cache_key(keys)][1:]
    assert column_keys(tables) == list(keys) and len(keys) % 128 == 0
    assert ok.tolist() == [True] * len(keys)  # pad and degraded columns are well-formed placeholders


def test_a_set_that_fills_its_tile_has_no_placeholder_to_lend():
    """128 keys leave no pad column; the 129th makes 127 of them, which
    are ONE new key, built once beside the key that joined."""
    v = TableBatchVerifier()
    full = list(range(128))
    for ranks, cost in ((full, {"device": 128}), (full[:40] + [128] + full[40:], {"host": 2})):
        before = keys_built()
        keys, _ = v._launch_keys(keys_of(ranks), True)
        tables, ok = v._tables_for(keys)
        assert rise(before, keys_built()) == cost
        assert column_keys(tables) == list(keys) and ok.all()
    assert len(keys) == 256 and keys.count(PLACEHOLDER_KEY) == 127


def test_the_sharded_verifier_reaches_the_same_build_for_a_set_padded_to_its_mesh():
    """`_tables_for_mesh` asks `_tables_for` for a set padded to 128
    columns a chip: a key that joins gets its own column there too."""
    from tendermint_tpu.parallel.mesh import MeshManager

    v = ShardedTableBatchVerifier(mesh=MeshManager(), min_device_batch=1)
    ndev = v.mesh.n_active
    assert ndev == 8
    for ranks in (G, JOIN_1 + list(range(17, 24))):  # 16, then 24: both split over 8 chips
        keys, _ = v._launch_keys(keys_of(ranks), True, ndev)
        assert len(keys) == 128 * ndev
        tables, ok = v._tables_for_mesh(keys, v.mesh.mesh())
        assert column_keys(tables) == list(keys) and ok.all()
    assert len(v._tables) == 2 and len(v._sharded_tables) == 2


# -- (b) a cycle's sets through one verifier ---------------------------------------


def cycle_sets(c: int) -> list[list[int]]:
    """`reactor_cycle`'s five steps with the c-th three standby keys:
    a joins, a is re-weighted, b and c join, b and c leave, a leaves."""
    a, b, cc = 16 + 3 * c, 17 + 3 * c, 18 + 3 * c
    ga = G[:5] + [a] + G[5:]
    gabc = G[:2] + [b] + G[2:5] + [a] + G[5:11] + [cc] + G[11:]
    return [ga, ga, gabc, ga, G]


def test_a_cycles_sets_cost_two_incremental_builds_of_three_keys_and_keep_the_genesis_set():
    v = TableBatchVerifier(min_device_batch=1)  # four sets, as a node's
    tables_of = lambda ranks: v._tables_for(v._launch_keys(keys_of(ranks), True)[0])  # noqa: E731
    tables_of(G)
    genesis_key = v._cache_key(v._launch_keys(keys_of(G), True)[0])
    for c in range(4):  # the first cycle costs what the later ones do: G is resident
        before_events, before_keys, t0 = table_events(), keys_built(), time.time()
        for ranks in cycle_sets(c):
            tables, ok = tables_of(ranks)
            assert column_keys(tables)[: len(ranks)] == keys_of(ranks) and ok.all()
        assert rise(before_events, table_events()) == {"hit": 3, "miss": 2, "incremental": 2}
        assert rise(before_keys, keys_built()) == {"host": 3}
        assert genesis_key in v._tables and len(v._tables) <= 4
        built = spans_since("tables.build", t0)
        assert [(s["attrs"]["kind"], s["attrs"]["keys_new"], s["attrs"]["columns"]) for s in built] == [
            ("incremental", 1, 128), ("incremental", 2, 128),
        ]


# -- (c) one build a set -----------------------------------------------------------


class HeldBuild:
    """`_build_tables` held until the test lets it go, counting its calls."""

    def __init__(self, verifier, fail: bool = False):
        self.real = verifier._build_tables
        self.fail = fail
        self.calls = 0
        self.started, self.go = threading.Event(), threading.Event()

    def __call__(self, pubkeys):
        self.calls += 1
        self.started.set()
        assert self.go.wait(WAIT_S)
        if self.fail:
            raise TableBuildError("the device's builder is down and the set is large")
        return self.real(pubkeys)


def joined_after(before: dict) -> bool:
    return rise(before, table_events()).get("joined", 0) >= 1


@pytest.mark.parametrize("fail", [False, True], ids=["sound_build", "failing_build"])
def test_prebuild_and_a_launch_of_one_set_build_it_once(monkeypatch, fail):
    """The launch finds the set's build in flight, started by
    `prebuild`, waits for it and takes its table. A build that fails
    releases the waiter onto the path a failed build takes: host crypto."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # `prebuild` pads as the TPU does
    v = TableBatchVerifier(min_device_batch=1)
    held = HeldBuild(v, fail)
    monkeypatch.setattr(v, "_build_tables", held)
    ranks = JOIN_1
    commit = commit_of(ranks, b"prebuild-race", forged=[5])
    before = table_events()
    try:
        v.prebuild(keys_of(ranks))
        assert held.started.wait(WAIT_S)
        v.prebuild(keys_of(ranks))  # asked again while in flight: no second thread
        out = []
        monkeypatch.setattr(tbl_mod, "verify_tables_kernel", lambda t, s, h, r: (out.append(t), np.ones(len(s), dtype=bool))[1])
        launch = threading.Thread(target=lambda: out.append(v.verify_commits(keys_of(ranks), [commit])))
        launch.start()
        wait_until(lambda: joined_after(before), timeout=WAIT_S, msg="the launch to find the build in flight")
        assert launch.is_alive()  # it waits, it does not build
        held.go.set()
        launch.join(WAIT_S)
        assert not launch.is_alive()
    finally:
        held.go.set()
    assert held.calls == 1 and v._building == {}
    assert rise(before, table_events()) == {"miss": 1, "joined": 1}
    verdicts = out[-1]
    if fail:
        # no table: the waiter answered with the host library, lane by lane
        assert len(out) == 1 and len(v._tables) == 0
        assert (verdicts[0] == reference(ranks, commit)).all() and not verdicts[0][5]
    else:
        (_keys, cached, _ok), = v._tables.values()
        assert out[0] is cached  # the launch ran on the prebuild's table


def test_many_threads_asking_for_a_few_sets_build_each_once():
    """More threads than cores, a short switch interval: a lost update
    in the in-flight dict would build a set twice or leave a waiter."""
    import sys

    v = TableBatchVerifier(cache_size=4)
    sets = [v._launch_keys(keys_of(G + [16 + j]), True)[0] for j in range(4)]
    builds: list = []
    real = v._build_tables

    def slow(pubkeys):
        builds.append(pubkeys)
        time.sleep(0.02)
        return real(pubkeys)

    v._build_tables = slow
    before, got, errors = table_events(), [], []

    def ask(i):
        try:
            for j in range(8):
                got.append((sets[(i + j) % 4], v._tables_for(sets[(i + j) % 4])[0]))
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=ask, args=(i,)) for i in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(WAIT_S)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and len(got) == 32 * 8
    assert sorted(builds) == sorted(sets) and v._building == {}
    events = rise(before, table_events())
    assert events["miss"] == 4 and events["hit"] + events.get("joined", 0) + 4 == 32 * 8
    for keys, tables in got:  # everyone got the one table of the set it asked for
        assert tables is v._tables[v._cache_key(keys)][1]


def build_kinds() -> dict:
    series = REGISTRY.to_dict()["tendermint_verify_table_build_seconds"]["series"]
    return {s["labels"]["kind"]: s["count"] for s in series}


def on_a_thread(name: str, fn) -> None:
    t = threading.Thread(target=fn, name=name)
    t.start()
    t.join(WAIT_S)
    assert not t.is_alive()


@pytest.mark.parametrize(
    "thread, kind, observed",
    [("blockchain-sync", "prebuild", "prebuild"), ("table-prebuild", None, "full")],
    ids=["a_prebuild_on_a_thread_of_another_name", "a_launchs_build_on_a_thread_of_that_name"],
)
def test_a_builds_kind_is_what_its_caller_says_whatever_its_threads_name(thread, kind, observed):
    v = TableBatchVerifier(min_device_batch=1)
    keys = v._launch_keys(keys_of(G), True)[0]
    before, t0 = build_kinds(), time.time()
    on_a_thread(thread, lambda: v._tables_for(keys, kind=kind))
    assert rise(before, build_kinds()) == {observed: 1}
    assert [(s["attrs"]["kind"], s["attrs"]["keys_new"]) for s in spans_since("tables.build", t0)] == [(observed, 128)]


def test_two_builds_interleaved_on_two_threads_each_report_their_own_keys():
    """One set joins a key to the cached one, the other shares nothing
    with it; neither build ends before both are under way."""
    v = TableBatchVerifier(min_device_batch=1)
    v._tables_for(v._launch_keys(keys_of(G), True)[0])
    joined = v._launch_keys(keys_of(JOIN_1), True)[0]
    apart = v._launch_keys(keys_of(range(100, 116)), True)[0]
    both, real = threading.Barrier(2), v._incremental_build

    def meet(pubkeys):
        both.wait(WAIT_S)
        built = real(pubkeys)
        both.wait(WAIT_S)
        return built

    v._incremental_build = meet
    before, t0 = keys_built(), time.time()
    threads = [threading.Thread(target=v._tables_for, args=(keys,)) for keys in (joined, apart)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(WAIT_S)
    assert not any(t.is_alive() for t in threads)
    spans = {s["attrs"]["kind"]: s["attrs"]["keys_new"] for s in spans_since("tables.build", t0)}
    assert spans == {"incremental": 1, "full": 128}
    assert rise(before, keys_built()) == {"host": 1, "device": 128}


# -- (d) fast-sync across the changes, the padded shape forced ---------------------


def rotating_chain(n_blocks: int, cycles: int = 2, every: int = 4):
    """A chain of 16 validators over the persistent kvstore whose blocks
    carry `cycles` of `reactor_cycle`'s five steps, one every `every`
    heights, with fresh standby keys each cycle; and the (pubkey, power)
    changes by height."""
    from tendermint_tpu.abci.apps import PersistentKVStoreApp
    from tendermint_tpu.types import PrivValidator

    from tests.helpers import ChainSim

    sim = ChainSim(n_vals=16, app=PersistentKVStoreApp())
    sim.privs.extend(PrivValidator(p) for p in PRIVS[16:])
    changes: dict[int, list[tuple[bytes, int]]] = {}
    for h in range(1, n_blocks + 1):
        step, c = divmod(h, every)[0] % 5, (h // every - 1) // 5
        if h % every == 0 and h <= 5 * every * cycles:
            a, b, cc = (PUBS[16 + 3 * c + j] for j in range(3))
            changes[h] = [[(a, 10)], [(a, 25)], [(b, 10), (cc, 10)], [(b, 0), (cc, 0)], [(a, 0)]][step - 1]
        txs = [b"val:%s/%d" % (k.hex().encode(), w) for k, w in changes.get(h, [])]
        sim.advance(txs=txs + [b"k%d=v%d" % (h, h)])
    return sim, changes


def test_fast_sync_crosses_every_set_change_at_the_padded_shape(monkeypatch):
    """Two cycles of `reactor_cycle` through `BlockchainReactor`, every
    window a launch of the table verifier at 128 columns: each applied
    header's `validators_hash` is the per-height reference's (plain set
    arithmetic and hashlib, `benchmark/lib/reference.py`), the app hash
    and the height are the source chain's, no block is redone and no
    peer dropped, and every window but the chain's last is cut at a
    set change."""
    from benchmark.lib import reference as plain
    from tendermint_tpu.abci.apps import PersistentKVStoreApp


    sim, changes = rotating_chain(46)
    assert sorted(changes) == list(range(4, 41, 4))
    genesis = [(v.pub_key.data, v.power) for v in sim.genesis.validators]
    address_of = {p.pub_key.data: p.pub_key.address for p in PRIVS} | {
        v.pub_key.data: v.pub_key.address for v in sim.genesis.validators
    }
    sets = plain.validator_sets(genesis, changes, address_of)
    assert [len(s["pubkeys"]) for s in sets[:6]] == [16, 17, 17, 19, 17, 16]

    # the kernel's stand-in answers each lane from the host library by the
    # key whose table the launch's column really holds
    launches = []

    def kernel(tables, s, h, r):
        keys = column_keys(tables)
        launches.append(len(s) // len(keys))
        out = np.zeros(len(s), dtype=bool)
        for lane in np.nonzero(s.any(axis=1))[0]:
            sig = bytes(r[lane]) + bytes(s[lane])
            out[lane] = PubKey(keys[lane % len(keys)]).verify(msg_of[sig], sig)
        return out

    msg_of = {}
    for block in sim.blocks[1:]:
        com = block.last_commit
        for msg, vote in zip(com.vote_sign_bytes(sim.chain_id), com.precommits):
            msg_of[vote.signature] = msg
    monkeypatch.setattr(tbl_mod, "verify_tables_kernel", kernel)
    monkeypatch.setattr(TableBatchVerifier, "_fused", staticmethod(lambda force: True))
    verifier = TableBatchVerifier(min_device_batch=1)
    reactor, state, store = _pipelined_reactor(sim, depth=2, verifier=verifier, app=PersistentKVStoreApp())
    redone = []
    monkeypatch.setattr(reactor, "_redo", redone.append)
    cuts = lambda: {c: counter("tendermint_fastsync_windows_total", cut=c) for c in ("full", "pool_gap", "boundary")}  # noqa: E731
    before_cuts, before_events, before_keys = cuts(), table_events(), keys_built()
    before_changes = {k: counter("tendermint_valset_changes_total", kind=k) for k in ("join", "leave", "power")}
    t0 = time.time()

    done = threading.Thread(target=reactor._try_sync)
    done.start()
    done.join(4 * WAIT_S)
    assert not done.is_alive()
    wait_until(lambda: not verifier._building, timeout=WAIT_S, msg="the last set's prebuild, which runs beside the sync")

    assert redone == [] and reactor.pool.num_peers() == 1
    assert store.height == state.last_block_height == 45
    for h in range(1, 46):
        header = store.load_block(h).header
        assert header.validators_hash == plain.set_at(sets, h)["validators_hash"], h
        assert header.app_hash == sim.blocks[h - 1].header.app_hash, h
    assert state.app_hash == sim.blocks[45].header.app_hash
    assert state.validators.hash() == plain.set_at(sets, 46)["validators_hash"] == sets[0]["validators_hash"]
    # ten boundaries: a window of 3 and the block before the change alone, each cut `boundary`
    assert rise(before_cuts, cuts()) == {"boundary": 20, "pool_gap": 1}
    assert sorted(set(launches)) == [1, 16] and launches.count(1) == 10
    # every launch's record names its heights, the block verified alone too (`_sync_one`)
    records = [r for r in LAUNCHLOG.recent(kind="tables") if r["t"] >= t0]
    assert sorted((r["height_lo"], r["height_hi"]) for r in records) == sorted(
        [(h, h) for h in changes] + [(h - 3, h - 1) for h in changes] + [(41, 45)]
    )
    assert {(r["k_launch"], r["n_launch"]) for r in records} == {(1, 128), (16, 128)}
    # (e) what the counters and spans say the sync did: four of a cycle's five steps change
    # the keys; two of those sets are not resident, and their three keys are built once
    events = rise(before_events, table_events())
    assert (events["miss"], events["incremental"]) == (1 + 4, 4) and events.get("host_build", 0) == 0
    assert rise(before_keys, keys_built()) == {"host": 6, "device": 128}
    got = {k: counter("tendermint_valset_changes_total", kind=k) - v for k, v in before_changes.items()}
    assert got == {"join": 6, "leave": 6, "power": 2}
    spans = spans_since("valset.change", t0)
    assert [s["attrs"]["height"] for s in spans] == sorted(changes)
    assert [(s["attrs"]["joined"], s["attrs"]["left"], s["attrs"]["reweighted"]) for s in spans[:5]] == [
        (1, 0, 0), (0, 0, 1), (2, 0, 0), (0, 2, 0), (0, 1, 0),
    ]
    assert [s["attrs"]["validators"] for s in spans[:5]] == [17, 17, 19, 17, 16]
    assert all(s["end"] >= s["start"] for s in spans)
