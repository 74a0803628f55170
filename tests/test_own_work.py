"""Time busy beside time waited, where the work happens: the series that
put a CPU clock beside a wall clock inside the program.

* a read's phases inside the RPC server
  (`tendermint_rpc_phase_seconds{method,phase}` and its CPU counter,
  `tendermint_rpc_response_bytes_total{method}`);
* a store's durable writes timed where they happen
  (`tendermint_db_commit_seconds{db}`; its count against
  `tendermint_db_commits_total{db}` is in test_store_crash.py);
* who has the interpreter (`tendermint_process_cpu_seconds_total`,
  `tendermint_process_thread_cpu_seconds{thread}`);
* a table build (`tendermint_verify_table_build_seconds{kind}`).

(The stopwatch itself is in test_telemetry.py, fast-sync's CPU counter in
test_fastsync_stages.py.)
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request
from types import SimpleNamespace

import numpy as np
import pytest

from tendermint_tpu.rpc.server import RPCError, RPCServer
from tendermint_tpu.telemetry import REGISTRY
from tendermint_tpu.telemetry import metrics as _metrics
from tendermint_tpu.telemetry import process as _process
from tendermint_tpu.telemetry.registry import CallbackCounter, Registry

from tests.helpers import THREAD_CLOCK_IS_FINE, cpu_slack

PHASE_SECONDS = "tendermint_rpc_phase_seconds"
PHASE_CPU = "tendermint_rpc_phase_cpu_seconds_total"
TOP = ("parse", "handle", "encode", "write")


def phases(method: str) -> dict:
    """`{phase: (count, seconds, cpu seconds)}` of `method` as it stands."""
    dump = REGISTRY.to_dict()
    cpu = {
        s["labels"]["phase"]: s["value"]
        for s in dump[PHASE_CPU]["series"]
        if s["labels"]["method"] == method
    }
    return {
        s["labels"]["phase"]: (s["count"], s["sum"], cpu.get(s["labels"]["phase"], 0.0))
        for s in dump[PHASE_SECONDS]["series"]
        if s["labels"]["method"] == method
    }


def rose(before: dict, method: str, want: dict, timeout: float = 5.0) -> dict:
    """How far each phase's count rose, once it reads `want` (`write`
    ends after the client has its answer: the server's thread may still
    be in it when the client returns)."""
    deadline = time.monotonic() + timeout
    while True:
        now = phases(method)
        got = {p: now[p][0] - before.get(p, (0, 0, 0))[0] for p in now}
        got = {p: n for p, n in got.items() if n}
        if got == want or time.monotonic() > deadline:
            return got
        time.sleep(0.01)


def get(port: int, path: str) -> tuple[int, bytes]:
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/{path}", timeout=10) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def post(port: int, method: str, **params) -> bytes:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/",
        data=json.dumps({"jsonrpc": "2.0", "id": 7, "method": method, "params": params}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        return resp.read()


@pytest.fixture
def server():
    def slow(ms: str = "20") -> dict:
        time.sleep(int(ms) / 1e3)
        return {"slept_ms": int(ms)}

    def refuses() -> dict:
        raise RPCError(-32000, "no such thing")

    def breaks() -> dict:
        raise ValueError("boom")

    routes = {
        "own_echo": lambda v="x": {"v": v},
        "own_slow": slow,
        "own_refuses": refuses,
        "own_breaks": breaks,
        "health": lambda: {"ready": True},
    }
    srv = RPCServer(routes, "tcp://127.0.0.1:0", event_switch=_events())
    srv.start()
    yield srv
    srv.stop()


def _events():
    from tendermint_tpu.types.events import EventSwitch

    return EventSwitch()


class TestRPCPhases:
    def test_a_get_and_a_post_each_observe_the_four_phases_once(self, server):
        before = phases("own_echo")
        status, body = get(server.port, "own_echo?v=hello")
        assert status == 200 and json.loads(body)["result"] == {"v": "hello"}
        assert rose(before, "own_echo", dict.fromkeys(TOP, 1)) == dict.fromkeys(TOP, 1)
        assert json.loads(post(server.port, "own_echo", v="again"))["result"] == {"v": "again"}
        assert rose(before, "own_echo", dict.fromkeys(TOP, 2)) == dict.fromkeys(TOP, 2)
        for phase, (_count, seconds, cpu) in phases("own_echo").items():
            assert 0 <= cpu <= seconds + cpu_slack(2), phase

    def test_handle_is_the_route_function_and_a_sleep_in_it_is_no_cpu(self, server):
        before = phases("own_slow")
        get(server.port, "own_slow?ms=50")
        assert rose(before, "own_slow", dict.fromkeys(TOP, 1)) == dict.fromkeys(TOP, 1)
        now = phases("own_slow")
        handle_s = now["handle"][1] - before.get("handle", (0, 0, 0))[1]
        handle_cpu = now["handle"][2] - before.get("handle", (0, 0, 0))[2]
        assert handle_s >= 0.05 and handle_cpu < 0.02 + cpu_slack()
        # the other three hold none of the route's 50 ms
        for phase in ("parse", "encode", "write"):
            assert now[phase][1] - before.get(phase, (0, 0, 0))[1] < 0.04, phase

    @pytest.mark.parametrize("route", ["own_refuses", "own_breaks"])
    def test_an_erroring_route_still_closes_its_phases(self, server, route):
        before = phases(route)
        status, body = get(server.port, route)
        assert status == 200 and "error" in json.loads(body)
        assert rose(before, route, dict.fromkeys(TOP, 1)) == dict.fromkeys(TOP, 1)

    def test_a_route_given_wrong_params_still_closes_its_phases(self, server):
        before = phases("own_echo")
        assert json.loads(get(server.port, "own_echo?nope=1")[1])["error"]["code"] == -32602
        assert rose(before, "own_echo", dict.fromkeys(TOP, 1)) == dict.fromkeys(TOP, 1)

    def test_no_route_of_the_table_goes_under_unknown_and_has_no_handle(self, server):
        before = phases("<unknown>")
        assert json.loads(get(server.port, "own_missing")[1])["error"]["code"] == -32601
        want = {"parse": 1, "encode": 1, "write": 1}
        assert rose(before, "<unknown>", want) == want
        # a body that is no JSON, and the route listing: no label of their own
        req = urllib.request.Request(f"http://127.0.0.1:{server.port}/", data=b"{not json")
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert json.load(resp)["error"]["code"] == -32700
        assert "own_echo" in json.loads(get(server.port, "")[1])["result"]
        want = {"parse": 3, "encode": 3, "write": 3}
        assert rose(before, "<unknown>", want) == want
        labels = {s["labels"]["method"] for s in REGISTRY.to_dict()[PHASE_SECONDS]["series"]}
        assert "own_missing" not in labels and "" not in labels

    def test_metrics_and_health_are_reads_too(self, server):
        before = {m: phases(m) for m in ("metrics", "health")}
        assert get(server.port, "metrics")[0] == 200
        assert get(server.port, "health")[0] == 200
        for method in ("metrics", "health"):
            want = dict.fromkeys(TOP, 1)
            assert rose(before[method], method, want) == want, method

    def test_response_bytes_are_the_bodys_length(self, server):
        name = "tendermint_rpc_response_bytes_total"
        before = REGISTRY.counter_value(name, method="own_echo")
        _, body = get(server.port, "own_echo?v=" + "z" * 1000)
        assert len(body) > 1000
        # `write` returns after the client has the body: wait for its count
        deadline = time.monotonic() + 5
        while REGISTRY.counter_value(name, method="own_echo") == before and time.monotonic() < deadline:
            time.sleep(0.01)
        assert REGISTRY.counter_value(name, method="own_echo") - before == len(body)

    def test_a_websocket_upgrade_observes_none(self, server):
        from tendermint_tpu.rpc.client import WSClient

        def all_counts():
            return {
                (s["labels"]["method"], s["labels"]["phase"]): s["count"]
                for s in REGISTRY.to_dict()[PHASE_SECONDS]["series"]
            }

        before = all_counts()
        ws = WSClient(f"127.0.0.1:{server.port}", reconnect=False)
        try:
            ws.subscribe("NewBlock")
        finally:
            ws.close()
        time.sleep(0.1)
        assert all_counts() == before

    def test_block_observes_load_and_render_inside_handle(self):
        from tendermint_tpu.blockchain import BlockStore
        from tendermint_tpu.db.kv import MemDB
        from tendermint_tpu.rpc.core import make_routes

        from tests.helpers import ChainSim

        sim = ChainSim(n_vals=4)
        store = BlockStore(MemDB())
        for h in range(3):
            block = sim.advance(txs=[b"k%d=v" % h])
            store.save_block(block, block.make_part_set(), sim.commits[-1])
        node = SimpleNamespace(
            block_store=store, config=SimpleNamespace(rpc=SimpleNamespace(unsafe=False))
        )
        srv = RPCServer(make_routes(node), "tcp://127.0.0.1:0")
        srv.start()
        try:
            before = phases("block")
            answer = json.loads(get(srv.port, "block?height=2")[1])["result"]["block"]
            assert answer["txs"] == [b"k1=v".hex()] and answer["header"]["height"] == 2
            want = {**dict.fromkeys(TOP, 1), "load": 1, "render": 1}
            assert rose(before, "block", want) == want
            now = phases("block")

            def seconds(phase):
                return now[phase][1] - before.get(phase, (0, 0, 0))[1]

            # the two children lie inside `handle`
            assert seconds("load") + seconds("render") <= seconds("handle")
            # a height the store has not: `load` alone, and the phases close
            assert "error" in json.loads(get(srv.port, "block?height=99")[1])
            want = {**dict.fromkeys(TOP, 2), "load": 2, "render": 1}
            assert rose(before, "block", want) == want
        finally:
            srv.stop()

    def test_the_old_handler_histogram_is_gone(self):
        assert REGISTRY.get("tendermint_rpc_request_seconds") is None
        assert not hasattr(_metrics, "RPC_SECONDS")


class TestCommitClock:
    def test_a_commit_is_timed_where_it_happens_and_a_failed_one_is_not(self, tmp_path):
        from tendermint_tpu.db.kv import SQLiteDB

        def read():
            dump = REGISTRY.to_dict()
            hist = [
                s for s in dump["tendermint_db_commit_seconds"]["series"]
                if s["labels"]["db"] == "clocked"
            ]
            return (
                REGISTRY.counter_value("tendermint_db_commits_total", db="clocked"),
                hist[0]["count"] if hist else 0,
                hist[0]["sum"] if hist else 0.0,
                REGISTRY.counter_value("tendermint_db_commit_cpu_seconds_total", db="clocked"),
            )

        db = SQLiteDB(str(tmp_path / "clocked.db"))
        try:
            before = read()
            db.set(b"a", b"1")
            batch = db.batch()
            batch.set(b"b", b"2")
            batch.set(b"c", b"3")
            batch.write()
            commits, count, seconds, cpu = (now - was for now, was in zip(read(), before))
            assert commits == count == 2
            assert 0 <= cpu <= seconds + cpu_slack(2)
            assert cpu > 0 or not THREAD_CLOCK_IS_FINE
            # a transaction that raises is rolled back: neither counted nor timed
            with pytest.raises(Exception):
                db._apply({b"d": "not bytes, not a blob".split()})
            assert tuple(now - was for now, was in zip(read(), before))[:2] == (2, 2)
            assert db.get(b"c") == b"3" and db.get(b"d") is None
        finally:
            db.close()


class TestWhoHasTheInterpreter:
    def test_the_process_total_rises_with_the_work_and_holds_every_thread(self):
        def total():
            return dict(REGISTRY.get("tendermint_process_cpu_seconds_total").samples())[()]

        before = total()
        end = time.perf_counter() + 0.05
        while time.perf_counter() < end:
            pass
        assert total() - before > 0.02

    def test_every_class_of_the_vocabulary_is_a_series(self):
        from tendermint_tpu.telemetry.profiler import SUBSYSTEMS

        text = REGISTRY.prometheus_text()
        for sub in SUBSYSTEMS:
            assert f'tendermint_process_thread_cpu_seconds{{thread="{sub}"}}' in text
        assert {"fastsync", "statesync", "txindex_merge", "rpc", "p2p_recv"} <= set(SUBSYSTEMS)

    def test_a_thread_is_filed_by_its_name_and_keeps_its_last_reading_when_it_exits(self):
        go, done = threading.Event(), threading.Event()

        def burn():
            end = time.perf_counter() + 0.05
            while time.perf_counter() < end:
                pass
            done.set()
            go.wait(10)

        def read(sub):
            return _process.thread_cpu_seconds()[(sub,)]

        before = read("fastsync")
        thread = threading.Thread(target=burn, name="fastsync", daemon=True)
        thread.start()
        assert done.wait(10)
        alive = read("fastsync")
        assert alive - before > 0.02
        go.set()
        thread.join()
        # it has exited: what was read for it stays, the series never falls
        assert read("fastsync") >= alive
        assert read("fastsync") >= alive
        # and state sync's thread is another row
        assert read("statesync") == _process.thread_cpu_seconds()[("statesync",)]

    def test_the_servers_connection_threads_are_named_and_filed_under_rpc(self, server):
        seen = {}

        def who() -> dict:
            seen["name"] = threading.current_thread().name
            end = time.perf_counter() + 0.03
            while time.perf_counter() < end:
                pass
            return {}

        server.routes["own_who"] = who
        before = _process.thread_cpu_seconds()[("rpc",)]
        # one connection kept open: its thread lives while we read the series
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=10)
        try:
            conn.request("GET", "/own_who")
            conn.getresponse().read()
            assert seen["name"] == "rpc-conn"
            assert _process.thread_cpu_seconds()[("rpc",)] - before > 0.01
        finally:
            conn.close()

    def test_a_connection_that_served_one_request_is_counted_whole(self, server):
        """No scrape sees such a thread alive: it reads its own clock as
        it leaves, and a scrape that catches it leaving counts it once."""

        def burn() -> dict:
            end = time.perf_counter() + 0.03
            while time.perf_counter() < end:
                pass
            return {}

        server.routes["own_burn"] = burn
        before = _process.thread_cpu_seconds()[("rpc",)]
        for _ in range(3):
            assert get(server.port, "own_burn")[0] == 200  # urllib keeps no connection
        deadline = time.monotonic() + 5
        while (
            any(t.name == "rpc-conn" for t in threading.enumerate())
            and time.monotonic() < deadline
        ):
            _process.thread_cpu_seconds()  # scrapes beside the exits
            time.sleep(0.005)
        rise = _process.thread_cpu_seconds()[("rpc",)] - before
        assert 0.05 < rise < 0.5

    def test_the_tx_indexs_merger_leaves_its_cpu_to_its_own_row(self, tmp_path):
        """A merger thread lives for its merges, between two scrapes as a
        rule: it reads its own clock as it leaves."""
        import hashlib

        from tendermint_tpu.db.runlog import FAN_IN, RunLog

        merges = "tendermint_txindex_merges_total"
        before = _process.thread_cpu_seconds()[("txindex_merge",)]
        merged = REGISTRY.counter_value(merges)
        log = RunLog(str(tmp_path / "txindex"))
        try:
            for height in range(1, FAN_IN + 1):
                keys = [hashlib.sha256(b"%d-%d" % (height, i)).digest() for i in range(200)]
                # every value `v` behind its length u32, five bytes apart
                log.append(height, b"".join(keys), b"\x01\0\0\0v" * 200, np.arange(200) * 5)
            deadline = time.monotonic() + 20
            while REGISTRY.counter_value(merges) == merged and time.monotonic() < deadline:
                time.sleep(0.01)
            assert REGISTRY.counter_value(merges) > merged
        finally:
            log.close()
        assert not any(t.name == "txindex-merge" for t in threading.enumerate())
        assert _process.thread_cpu_seconds()[("txindex_merge",)] > before

    def test_a_callback_counter_never_reads_lower_and_survives_a_failing_read(self):
        reads = iter([{("a",): 2.0}, {("a",): 1.0, ("b",): 5.0}, RuntimeError("gone")])

        def fn():
            item = next(reads)
            if isinstance(item, Exception):
                raise item
            return item

        family = CallbackCounter("own_total", "help", fn, labelnames=("k",), registry=Registry())
        assert dict(family.samples()) == {("a",): 2.0}
        assert dict(family.samples()) == {("a",): 2.0, ("b",): 5.0}
        assert dict(family.samples()) == {("a",): 2.0, ("b",): 5.0}
        assert family.type_name == "counter"


class TestTableBuildKinds:
    """`tendermint_verify_table_build_seconds{kind}`: a miss of the table
    cache is one observation, under the way the build went."""

    @staticmethod
    def counts() -> dict:
        return {
            s["labels"]["kind"]: s["count"]
            for s in REGISTRY.to_dict()["tendermint_verify_table_build_seconds"]["series"]
        }

    @staticmethod
    def keys(n: int, salt: int = 0) -> tuple[bytes, ...]:
        from tendermint_tpu.crypto.keys import gen_priv_key

        rng = np.random.default_rng(1000 + salt)
        return tuple(gen_priv_key(rng.bytes(32)).pub_key.data for _ in range(n))

    def rise(self, before: dict) -> dict:
        return {k: n - before[k] for k, n in self.counts().items() if n != before[k]}

    def test_the_four_kinds(self, monkeypatch):
        from tendermint_tpu.ops import ed25519_tables
        from tendermint_tpu.services.verifier import TableBatchVerifier

        # the device build's executable takes minutes to compile on the
        # CPU: the host build stands in for it, the paths around it are
        # the verifier's own
        def device_build(pub):
            import jax.numpy as jnp

            t, ok = ed25519_tables.host_build_key_tables([bytes(r) for r in pub])
            return jnp.asarray(t), ok

        monkeypatch.setattr(ed25519_tables, "build_key_tables", device_build)
        assert set(self.counts()) == {"full", "incremental", "host_build", "prebuild"}
        v = TableBatchVerifier()
        base = self.keys(4)
        before = self.counts()
        v._tables_for(base)
        assert self.rise(before) == {"full": 1}
        v._tables_for(base)  # a hit builds nothing
        assert self.rise(before) == {"full": 1}
        v._tables_for(base + self.keys(1, salt=1))
        assert self.rise(before) == {"full": 1, "incremental": 1}
        # the thread `apply_block` starts when a block changes the set
        grown = base + self.keys(2, salt=2)
        v.prebuild(grown)
        deadline = time.monotonic() + 60
        while "prebuild" not in self.rise(before) and time.monotonic() < deadline:
            time.sleep(0.02)
        assert self.rise(before) == {"full": 1, "incremental": 1, "prebuild": 1}
        # behind an open breaker the build is the host's
        for _ in range(10):
            v._build_breaker.record_failure()
        assert not v._build_breaker.allow()
        v._tables_for(self.keys(3, salt=3))
        assert self.rise(before) == {"full": 1, "incremental": 1, "prebuild": 1, "host_build": 1}
