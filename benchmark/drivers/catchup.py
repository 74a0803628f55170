"""Driver `catchup`: a fresh node fast-syncs a seeded chain from the
deployment's serving peers (`"peers"` in its file; one in every listed cell).

The node under test is `tendermint_tpu.node.Node`, built and started in
this process through the calls `cmd.py::_cmd_node` makes
(`enable_persistent_cache()`, `load_config`, `Node(cfg).start()`), so the
profiler trace is this process's own. The serving peers (`lib/peer.py`,
a child each; the first also generates the chain) and the read client
(`lib/client.py`) are children; none touches the chip. The window is
observed over RPC. What a peer answers is the mix's peer rule
(`lib/peers.py`; none: the generator's bytes), and the node is held to it:
a debit of a peer that lied is due, one of a peer that did not is a failure.

Set-up, in order: peer children (chain from the seed) -> JAX up here, no
accelerator is a refusal -> node home, node start with the peers as its
seeds, after one verifier call per window size -> client child -> wait until the node has applied the mix's
`warm_blocks`. Then `--seconds` of catch-up, then, outside the window,
the checks.
"""

from __future__ import annotations

import gc
import json
import os
import re
import shutil
import signal
import statistics
import subprocess
import sys
import tempfile
import threading
import time

CHAINS_KEPT = 4
PEER_UP = re.compile(r"peer up: p2p :(\d+)")
SLOW_READ_S = 1.0
# the first run of a cell in a checkout compiles inside the warm-up
WARM_WAIT_S = 300.0


class Child:
    """A child process with its output in a log file."""

    def __init__(self, name: str, argv: list[str], env: dict, cwd: str, log_dir: str, pipes: bool = False):
        self.name = name
        self.log_path = os.path.join(log_dir, f"{name}.log")
        self._log = open(self.log_path, "wb")
        if pipes:
            self.proc = subprocess.Popen(
                argv, env=env, cwd=cwd, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=self._log, text=True,
            )
        else:
            self.proc = subprocess.Popen(
                argv, env=env, cwd=cwd, stdout=self._log, stderr=subprocess.STDOUT
            )

    def output(self) -> str:
        with open(self.log_path, "r", errors="replace") as f:
            return f.read()

    def stop(self, grace: float = 15.0) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(grace)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(10)
        for stream in (self.proc.stdin, self.proc.stdout):
            if stream is not None:
                stream.close()
        if not self._log.closed:
            self._log.close()


class RunFailure(Exception):
    pass


def _child_env(root: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = root + (os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["PYTHONUNBUFFERED"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    return env


def peer_commands(here: str, cell: dict, seed: int, peer_home: str, n_peers: int) -> list[list[str]]:
    """The command line of each serving peer. One peer: the line it always
    had. Of several, each is told which it is (`--index`, `--of`)."""
    workers = max(1, min(10, (os.cpu_count() or 2) - 3))
    argv = [
        sys.executable, "-m", "benchmark.lib.peer",
        "--home", peer_home,
        "--config", os.path.join(here, "configs", cell["config"] + ".json"),
        "--mix", os.path.join(here, "traffic", cell["traffic"] + ".json"),
        "--seed", str(seed), "--blocks", str(int(cell["chain_blocks"])), "--workers", str(workers),
    ]
    if n_peers == 1:
        return [argv]
    return [[*argv, "--index", str(i), "--of", str(n_peers)] for i in range(n_peers)]


def seeds(p2p_ports: list[int]) -> str:
    """The node's `p2p.seeds`: every serving peer's address (`node/node.py`
    splits the list at its commas)."""
    return ",".join(f"127.0.0.1:{port}" for port in p2p_ports)


def _evict_chains(chains_dir: str, keep: str) -> None:
    """The chain cache holds the newest few chains: a check draws a new
    seed for every run, and a 1,024-validator chain is hundreds of MB."""
    if not os.path.isdir(chains_dir):
        return
    entries = sorted(
        (e for e in os.scandir(chains_dir) if e.is_dir() and e.name != keep),
        key=lambda e: e.stat().st_mtime,
        reverse=True,
    )
    for e in entries[CHAINS_KEPT - 1 :]:
        shutil.rmtree(e.path, ignore_errors=True)


def _wait(cond, timeout: float, what: str, child: Child | None = None, tick: float = 0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = cond()
        if got:
            return got
        if child is not None and child.proc.poll() is not None:
            raise RunFailure(
                f"{child.name} exited with {child.proc.returncode} while waiting for {what}:\n"
                + child.output()[-3000:]
            )
        time.sleep(tick)
    raise RunFailure(f"timed out after {timeout:.0f}s waiting for {what}")


def _warm_shapes(record, seed: int, log) -> tuple[dict, list[str]]:
    """One call of the process's verifier per window size K = 1..16 at the
    validator set of the chain's tail, on the chain's last commits (heights
    the node never reaches in a run). Which K a catch-up launches depends on when
    blocks arrive, and every K the kernel does not pad is an executable of
    its own: one first met inside the window stalls it for the seconds
    this returns per K. Each call's last commit carries one flipped
    signature bit, so no call finds all its lanes in the
    verified-signature cache (only valid signatures are cached), and each
    must be refused at exactly that lane. (Three calls at a time took as
    long as one after another: my chip run, PR 23.)"""
    from benchmark.lib import chain as chainlib
    from benchmark.lib.checks import REFUSED, validator_set
    from tendermint_tpu.services.verifier import default_verifier
    from tendermint_tpu.types.errors import ValidationError

    valset = validator_set(record)
    entries = record.tail_entries()
    verifier = default_verifier()
    wrong: list[str] = []

    def one(k: int) -> float:
        t = time.monotonic()
        bid, height, commit = entries[k - 1]
        forged, idx = chainlib.tamper(commit, seed + k)
        try:
            valset.verify_commit_batched(
                record.chain_id, [*entries[: k - 1], (bid, height, forged)], verifier
            )
        except ValidationError as e:
            m = REFUSED.search(str(e))
            if k > 1 and (not m or (int(m.group(1)), int(m.group(3))) != (idx, height)):
                wrong.append(f"warm shapes, K={k}: refused as {e}")
        else:
            wrong.append(f"warm shapes, K={k}: a flipped signature bit was accepted")
        return round(time.monotonic() - t, 3)

    t0 = time.monotonic()
    seconds = {f"K={k}": one(k) for k in range(1, len(entries) + 1)}
    seconds["all"] = round(time.monotonic() - t0, 3)
    log(f"warm shapes, seconds per K: {json.dumps(seconds)}; {len(wrong)} wrong verdicts (limit 0)")
    return seconds, wrong


class GcPauses:
    """Every collection of the interpreter the node shares with this driver,
    timed by `gc.callbacks`: a collection stops every thread of the node at
    once, and a slow read is then no fault of the RPC layer's."""

    def __init__(self) -> None:
        self.pauses: list[tuple[int, float]] = []  # (generation, seconds)
        self._t = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.monotonic()
        else:
            self.pauses.append((int(info["generation"]), time.monotonic() - self._t))


def _bytes_written() -> dict:
    """What this process (the node is in it) has written so far, by the
    kernel's two counts: `wchar`, the bytes its write calls carried (files,
    sockets and pipes alike), and `write_bytes`, what of that went down to
    a block device (0 on a machine whose file system is not one). The
    driver counts a machine's writes, and a block of 10,000 txs is 10,000
    rows of the tx index."""
    out = {}
    try:
        with open("/proc/self/io") as f:
            for row in f:
                name, _, value = row.partition(":")
                if name in ("wchar", "write_bytes"):
                    out[name] = int(value)
    except OSError:
        pass
    return out


def _longest_standstill(status: list[dict]) -> float:
    """The longest time, by the client's `/status` answers, over which the
    applied height stayed where it was."""
    seen: dict[int, list[float]] = {}
    for r in status:
        seen.setdefault(r["height"], [r["end"], r["end"]])[1] = r["end"]
    return max((hi - lo for lo, hi in seen.values()), default=0.0)


class PeerWatch(threading.Thread):
    """Under a peer rule only: who the node has debited and how many peers
    it holds, looked at twenty times a second, a row a change, with the
    height the store and the pool stood at. The switch's counter names no
    peer and the node's log is held to errors, so this is where "who, at
    which height" comes from."""

    def __init__(self, node) -> None:
        super().__init__(name="bench-peer-watch", daemon=True)
        self.node, self.rows, self._halt = node, [], threading.Event()

    def run(self) -> None:
        last = None
        pool = self.node.blockchain_reactor.pool
        while not self._halt.wait(0.05):
            now = (tuple(_debited(self.node)), len(self.node.switch.peers()), pool.num_peers())
            if now != last:
                last = now
                self.rows.append({
                    "wall": time.time(), "store_height": self.node.block_store.height,
                    "pool_height": pool.height, "debited": list(now[0]), "connected": now[1], "pool_peers": now[2],
                })

    def stop(self) -> list[dict]:
        self._halt.set()
        self.join(5)
        return self.rows


def _debited(node) -> list[str]:
    """The node ids the node's scorer holds a score or a ban for."""
    held = node.switch.scorer.snapshot()
    return sorted({*held["scores"], *held["bans"]})


def _judge_peers(node, serving, peer_home, connected, metrics, h_close, record, events, log) -> dict:
    """After the window: the three numbers `correct` holds the node's
    treatment of its peers to, beside their limits (`lib/peers.py`
    `account`, `checks.forged_blocks_applied`), the failures in words, what
    `failed` counts of the debits, and how many peers served and how many
    the node ended with; `events` are `PeerWatch`'s rows, where it ran. A
    peer is named by its index among the serving peers; a node id that is
    none of theirs by its first 12 characters."""
    from benchmark.lib import checks, rpc
    from benchmark.lib import peers as peerlib
    from tendermint_tpu.p2p.node_key import NodeKey

    index_of = {
        NodeKey.load_or_gen(os.path.join(peer_home, peerlib.key_file(i))).node_id: i for i in range(len(serving))
    }

    def named(node_ids) -> list:
        return sorted((index_of.get(i, i[:12]) for i in node_ids), key=str)

    lies = {i: peerlib.lies_sent(c.output()) for i, c in enumerate(serving)}
    got = peerlib.account(
        lies=lies, debited=set(named(_debited(node))),
        debits=int(rpc.metric(metrics, "tendermint_p2p_peer_misbehavior_total")),
        connected=set(named(connected)), h_close=h_close,
    )
    forged = checks.forged_blocks_applied(node.block_store, record)
    ended = {
        "served": len(serving), "connected_at_close": len(connected),
        "pool_peers_at_end": node.blockchain_reactor.pool.num_peers(),
    }
    by_kind = {
        ls.get("kind", ""): int(v) for ls, v in metrics.get("tendermint_p2p_peer_misbehavior_total", []) if v
    }
    log(
        f"check peers: {json.dumps(ended)}; debited {got['debited']} (debits by kind, at the window's close: "
        f"{json.dumps(by_kind)}), {len(got['undue'])} of them gave no "
        f"unsound answer (limit 0); peers that lied {got['liars']}, {len(got['kept'])} of them still connected at "
        f"the window's end after a lie the node got past (limit 0); {len(forged)} applied heights hold another "
        f"block than the source chain's (limit 0){': ' + str(forged[:8]) if forged else ''}"
    )
    failures = []
    if forged:
        failures.append(f"{len(forged)} applied heights hold another block than the source chain's: {forged[:8]}")
    if got["undue"]:
        failures.append(f"the node debited peers that gave no unsound answer: {got['undue']}")
    if got["kept"]:
        failures.append(f"the node still held peers that lied to it: {got['kept']}")
    notes = {
        **ended, "lies_sent": {i: sent for i, sent in lies.items() if sent}, "debited": got["debited"],
        "debits_by_kind": by_kind,
    }
    if events is not None:
        notes["events"] = [{**row, "debited": named(row["debited"])} for row in events]
        for row in notes["events"]:
            log(f"peer event: {json.dumps(row)}")
    return {
        "compared": {
            "forged_blocks_applied": [len(forged), 0],
            "peers_debited_undue": [len(got["undue"]), 0],
            "liars_kept": [len(got["kept"]), 0],
        },
        "failures": failures, "debits_undue": got["debits_undue"], "ended": ended, "notes": notes,
    }


def run(ctx: dict) -> dict | None:
    log = ctx["log"]
    root, here = ctx["root"], ctx["here"]
    cell, config, mix, seed = ctx["cell"], ctx["config"], ctx["mix"], ctx["seed"]
    sys.path.insert(0, root)
    from benchmark.lib import chain as chainlib
    from benchmark.lib import peers as peerlib
    from benchmark.lib import rpc
    from benchmark.lib.stats import percentile

    cache = os.path.join(here, "lib", "cache")
    work_root = os.path.join(here, "out", "work")
    os.makedirs(work_root, exist_ok=True)
    work = tempfile.mkdtemp(prefix=f"{cell['name']}-{seed}-", dir=work_root)
    children: list[Child] = []
    node = watch = None
    n_blocks = int(cell["chain_blocks"])
    try:
        # -- 1. the peer child: the chain from the seed, then it serves ----
        key = chainlib.chain_key(cell["config"], cell["traffic"], seed, n_blocks)
        chains_dir = os.path.join(cache, "chains")
        peer_home = os.path.join(chains_dir, key)
        os.makedirs(chains_dir, exist_ok=True)
        _evict_chains(chains_dir, key)
        if os.path.isdir(peer_home) and not os.path.exists(os.path.join(peer_home, "record.json")):
            shutil.rmtree(peer_home)  # a generation that was cut short
        n_peers = peerlib.count(config)
        serving = [
            Child("peer" if i == 0 else f"peer{i}", argv, _child_env(root), root, work)
            for i, argv in enumerate(peer_commands(here, cell, seed, peer_home, n_peers))
        ]
        children.extend(serving)

        # -- 2. JAX up in this process; no accelerator is a refusal --------
        if ctx["control"] == "host_answers":
            # the control: every commit-shaped batch goes to the host library
            os.environ["TENDERMINT_TPU_MIN_DEVICE_BATCH"] = str(1 << 40)
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cache, "jax")
            os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
        from tendermint_tpu.utils.jax_cache import enable_persistent_cache

        enable_persistent_cache()
        import jax

        try:
            devices = jax.devices()
        except RuntimeError as e:
            print(f"benchmark: JAX could not start a backend: {e}", file=sys.stderr)
            return None
        platform, kind = devices[0].platform, devices[0].device_kind
        if platform == "cpu" and not ctx["allow_cpu"]:
            print("benchmark: JAX sees no accelerator; nothing was run", file=sys.stderr)
            return None
        if len(devices) < int(cell["chips"]) and not ctx["allow_cpu"]:
            print(
                f"benchmark: the cell asks for {cell['chips']} chips, JAX sees {len(devices)}",
                file=sys.stderr,
            )
            return None
        ctx["device_tag"]["text"] = f"platform={platform} device_kind={kind!r} devices={len(devices)}"
        t_jax = time.monotonic() - ctx["t0"]
        log(f"backend up after {t_jax:.1f}s")

        if ctx["control"] == "accept_all":
            from benchmark.lib.controls import install_accept_all

            install_accept_all()

        # -- 3. the peers are up ----------------------------------------------
        p2p_ports = [
            int(_wait(lambda c=c: PEER_UP.search(c.output()), 900.0, f"the {c.name}", c, tick=0.2).group(1))
            for c in serving
        ]
        t_peer = time.monotonic() - ctx["t0"]
        record = chainlib.Record.load(os.path.join(peer_home, "record.json"))
        log(
            f"peer up after {t_peer:.1f}s: {n_blocks} blocks, built in "
            f"{record.build_seconds:.1f}s ({chainlib.digest(record)})"
        )
        if peerlib.keeps_notes(mix, n_peers):
            log(f"{n_peers} serving peers on ports {p2p_ports}, peer rule {json.dumps(mix.get('peers'))}")
        if ctx["control"] == "apphash_off_by_one":
            record.app_hash = [""] + record.app_hash[:-1]

        # -- 4. the node under test, in this process, as cmd.py starts it ---
        from tendermint_tpu.config import Config, load_config, write_config
        from tendermint_tpu.node import Node

        node_home = os.path.join(work, "node")
        os.makedirs(node_home)
        cfg = Config.default(node_home)
        cfg.base.log_level = "*:error"
        cfg.base.fast_sync = True
        cfg.p2p.laddr = "tcp://127.0.0.1:0"
        cfg.rpc.laddr = "tcp://127.0.0.1:0"
        cfg.p2p.pex = False
        cfg.p2p.send_rate = cfg.p2p.recv_rate = int(config["p2p_rate_bytes_per_s"])
        cfg.p2p.seeds = seeds(p2p_ports)
        write_config(cfg)
        shutil.copy(os.path.join(peer_home, "genesis.json"), cfg.genesis_path())
        cfg = load_config(node_home)
        hasher = None  # the node's own choice: the device's trees where a TPU is up
        if ctx["control"] == "host_trees":
            from benchmark.lib.controls import host_tree_hasher

            hasher = host_tree_hasher()
        # the app the deployment names (the node's own default is the
        # kvstore); a persistent app keeps its state in the node's home
        app = chainlib.make_app(config["app"], cfg.db_path("app"))
        node = Node(cfg, app=app, hasher=hasher)
        shape_seconds, shape_failures = _warm_shapes(record, seed, log)
        t_node = time.monotonic()
        node.start()
        port = node.rpc_port
        log(f"node {node.node_id[:12]} up: rpc :{port}")
        if "peers" in mix:
            watch = PeerWatch(node)
            watch.start()

        # -- 5. the client child ---------------------------------------------
        reads = mix["reads"]
        client = Child(
            "client",
            [
                sys.executable, os.path.join(here, "lib", "client.py"),
                "--port", str(port), "--per-s", str(reads["per_s"]),
                "--kinds", ",".join(reads["kinds"]), "--seed", str(seed),
            ],
            {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}, root, work, pipes=True,
        )
        children.append(client)
        if client.proc.stdout.readline().strip() != "client ready":
            raise RunFailure("the client did not start:\n" + client.output()[-2000:])

        # -- 6. warm-up: the node syncs the mix's first heights ---------------
        warm = int(mix["warm_blocks"])
        store = node.block_store
        t_first = []

        progress = [time.monotonic()]

        def warmed() -> bool:
            h = store.height
            if h >= 1 and not t_first:
                t_first.append(time.monotonic() - t_node)
            if time.monotonic() - progress[0] > 20.0:
                progress[0] = time.monotonic()
                pool = node.blockchain_reactor.pool
                log(
                    f"warming up: height {h}, pool at {pool.height}, {pool.num_peers()} peers, "
                    f"peer tip {pool.max_peer_height()}"
                )
            return h >= warm

        try:
            _wait(warmed, WARM_WAIT_S, f"height {warm}", tick=0.05)
        except RunFailure:
            import faulthandler

            faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
            print("---- peer log ----\n" + serving[0].output()[-3000:], file=sys.stderr)
            raise
        t_first_block = t_first[0]
        setup_s = time.monotonic() - ctx["t0"]
        h_open = store.height
        usable = n_blocks - 2 * chainlib.FAULT_WINDOW - 2
        log(
            f"set-up done after {setup_s:.1f}s: height {h_open}, first block applied "
            f"{t_first_block:.1f}s after node start"
        )
        if h_open >= usable:
            raise RunFailure(f"chain exhausted in set-up: height {h_open} of {n_blocks}")

        # -- 7. the window -----------------------------------------------------
        seconds = float(ctx["seconds"])
        metrics_start = rpc.pull_metrics(port)
        pauses = GcPauses()
        gc.callbacks.append(pauses)
        wall_start = time.time()
        client.proc.stdin.write(f"go {seconds}\n")
        client.proc.stdin.flush()
        t_go = time.monotonic()
        closed: dict = {}

        def close_window() -> None:
            # on a thread of its own: in a traced run the main thread may
            # still be inside stop_trace when the window ends
            closed.update(
                wall=time.time(), height=store.height, metrics=rpc.pull_metrics(port),
                connected={p.id for p in node.switch.peers()},
            )

        closer = threading.Timer(seconds, close_window)
        closer.start()
        trace_info = None
        if ctx["trace"]:
            # the middle of the window, as long as the cell says (half the
            # window where it does not): at a few blocks a second a
            # 16-commit launch comes every few seconds, and a stretch with
            # no launch in it has no device plane at all; at tens of blocks
            # a second ten seconds of trace take the profiler as long again
            # to write, inside the window. The Python tracer
            # is off: with it on the host ran at half its speed (my chip
            # run, PR 23) and the traced numbers were of another program.
            stretch = min(seconds, float(cell.get("trace_seconds", seconds / 2)))
            time.sleep(max(0.0, (seconds - stretch) / 2))
            trace_dir = os.path.join(work, "trace")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            trace_wall0 = time.time()
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            t_tr = time.monotonic()
            time.sleep(stretch)
            traced_s = time.monotonic() - t_tr
            jax.profiler.stop_trace()
            trace_info = {"dir": trace_dir, "wall0": trace_wall0, "seconds": traced_s}
        window = json.loads(client.proc.stdout.readline())
        closer.join()
        gc.callbacks.remove(pauses)
        t_window = time.monotonic() - t_go
        wall_end, h_close, metrics_end = closed["wall"], closed["height"], closed["metrics"]
        dump = rpc.call(port, "dump_telemetry?launches=1024")
        _, body = rpc.http_get(f"http://127.0.0.1:{port}/health")
        health = json.loads(body)
        launches_all = (dump.get("launches") or {}).get("records", [])
        launches = [r for r in launches_all if wall_start <= float(r.get("t", 0.0)) <= wall_end]
        from benchmark.lib import ledger

        sizes: dict = {}
        for r in ledger.tagged({"launches": launches}):
            sizes[ledger.commits(r)] = sizes.get(ledger.commits(r), 0) + 1
        log(
            f"window closed after {t_window:.2f}s: height {h_open} -> {h_close}, "
            f"{len(launches)} of {len(launches_all)} ledger records inside it, "
            f"verify launches by commits carried: {json.dumps(dict(sorted(sizes.items())))}"
        )

        # -- 8. the numbers ----------------------------------------------------
        rd = window["reads"]
        status = [r for r in rd if r["kind"] == "status" and r["ok"]]
        # a read that answered, and answered right, did not fail however long
        # it took: its latency is in entry.rpc_p95_ms and its count in
        # entry.reads_over_1s. `failed` counts reads that errored or timed out.
        bad_reads = [r for r in rd if not r["ok"]]
        slow_reads = [r for r in rd if r["ok"] and r["end"] - r["due"] > SLOW_READ_S]
        latencies_ms = [(r["end"] - r["due"]) * 1e3 for r in rd if r["ok"]]
        late_ms = [(r["start"] - r["due"]) * 1e3 for r in rd]
        end_to_end = {"setup_s": {"value": setup_s, "unit": "s"}}
        if len(status) >= 2 and status[-1]["end"] > status[0]["end"]:
            rate = (status[-1]["height"] - status[0]["height"]) / (status[-1]["end"] - status[0]["end"])
            end_to_end["catchup_blocks_per_s"] = {"value": rate, "unit": "blocks/s"}
        log(
            f"reads: {len(rd)} issued, {len(bad_reads)} failed, "
            f"{len(slow_reads)} answered after over {SLOW_READ_S:.0f}s, "
            f"median {statistics.median(latencies_ms) if latencies_ms else float('nan'):.2f} ms, "
            f"p95 {percentile(latencies_ms, 95) if latencies_ms else float('nan'):.2f} ms, "
            f"generator late by median {statistics.median(late_ms):.2f} ms, max {max(late_ms):.2f} ms"
        )
        old = [s for g, s in pauses.pauses if g == 2]
        standstill = _longest_standstill(status)
        log(
            f"interpreter: {len(pauses.pauses)} collections in the window took "
            f"{sum(s for _g, s in pauses.pauses):.3f}s, {len(old)} of the oldest generation "
            f"{sum(old):.3f}s (longest {max(old, default=0.0):.3f}s); the height stood still "
            f"for at most {standstill:.3f}s"
        )
        table_events = {
            event: int(rpc.rise(metrics_start, metrics_end, "tendermint_verify_table_cache_total", event=event))
            for event in ("hit", "miss", "incremental", "host_build")
        }
        log(f"table cache: tendermint_verify_table_cache_total rose inside the window by {json.dumps(table_events)}")
        written = _bytes_written()
        log(f"storage: this process has written, in bytes since it started: {json.dumps(written)}")
        for name, m in end_to_end.items():
            log(f"end-to-end {name} = {m['value']} {m['unit']}")

        # -- 9. the trace ------------------------------------------------------
        reduced = None
        notes: dict = {
            "heights": [h_open, h_close], "setup_parts_s": {
                "backend_up": t_jax, "peer_up": t_peer, "chain_build": record.build_seconds,
                "node_start_to_first_block": t_first_block, "warm_shapes": shape_seconds,
            },
            "window_s": t_window, "launch_sizes": sizes,
            "gc": {"collections": len(pauses.pauses), "seconds": sum(s for _g, s in pauses.pauses),
                   "oldest": len(old), "oldest_seconds": sum(old), "oldest_longest_s": max(old, default=0.0)},
            "longest_standstill_s": standstill, "process_write_bytes": written,
            "table_cache_events": table_events,
        }
        if trace_info is not None:
            from benchmark.lib import trace_reduce

            xplane = trace_reduce.find_xplane(trace_info["dir"])
            if xplane is not None:
                raw = trace_reduce.extract(xplane)
                reduced = trace_reduce.reduce(raw, trace_info["seconds"])
                notes["trace_planes"] = raw["plane_names"]
                notes["trace_lines"] = {
                    p["name"]: {ln["name"]: len(ln["events"]) for ln in p["lines"]}
                    for p in raw["planes"]
                }
            if reduced is not None:
                reduced["wall0"] = trace_info["wall0"]
                log(
                    f"trace: {reduced['busy_s']:.4f}s busy of {reduced['window_s']:.3f}s "
                    f"on {reduced['chips']} chip(s)"
                )

        # -- 10. correct? --------------------------------------------------------
        from benchmark.lib import checks

        obs = {
            "cell": cell, "config": config, "mix": mix, "seed": seed, "seconds": seconds,
            "reads": rd, "launches": launches, "metrics_start": metrics_start,
            "metrics_end": metrics_end, "trace": reduced, "record": record,
            "device_kind": kind, "window": [wall_start, wall_end], "heights": [h_open, h_close],
        }
        checked = checks.run_checks(
            port=port, record=record, config=config, mix=mix, seed=seed,
            static_reference=ctx["control"] == "static_reference",
            h_close=h_close, launches=launches_all, metrics=metrics_end, health=health,
            devices=devices, log=log,
        )
        obs["host_fallbacks"] = checked["host_fallbacks"]
        obs["hash_host_fallbacks"] = checked["hash_host_fallbacks"]
        failures = shape_failures + checked["failures"]
        compared = {"warm_shape_wrong_verdicts": [len(shape_failures), 0], **checked["compared"]}
        compared["chain_exhausted"] = [int(h_close >= usable), 0]
        if h_close >= usable:
            failures.append(f"chain exhausted: height {h_close} of {n_blocks} inside the window")
        compared["no_rate_read"] = [int("catchup_blocks_per_s" not in end_to_end), 0]
        if "catchup_blocks_per_s" not in end_to_end:
            failures.append("no two /status reads answered inside the window")
        # the node's treatment of its peers against what each of them did
        peers_seen = _judge_peers(
            node, serving, peer_home, closed["connected"], metrics_end, h_close, record,
            watch.stop() if watch is not None else None, log,
        )
        compared.update(peers_seen["compared"])
        failures += peers_seen["failures"]
        notes["peers"] = peers_seen["notes"]
        if peerlib.keeps_notes(mix, n_peers):
            # a peer of several, or under a rule, says at its end what it served
            for child in serving:
                child.stop()
            notes["peers"]["heights_served"] = {
                i: peerlib.heights_served(child.output()) for i, child in enumerate(serving)
            }
        for f in failures[:8]:
            log(f"NOT CORRECT: {f}")
        if len(failures) > 8:
            log(f"NOT CORRECT: and {len(failures) - 8} more")
        heights_fetched = max(0, h_close - h_open)
        stats = devices[0].memory_stats() or {}
        peak = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices
        ) if stats else 0
        device = {"platform": platform, "kind": kind, "count": len(devices), "memory_peak_bytes": peak}
        breakdown = None
        if reduced is not None:
            from benchmark.lib import trace_reduce

            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = {
                "device_ops": trace_reduce.top_ops(reduced),
                "idle_gaps": trace_reduce.label_gaps(reduced, reduced["wall0"], launches_all),
            }
        obs.update(
            end_to_end=end_to_end, correct=not failures, attempted=heights_fetched + len(rd),
            failed=len(bad_reads) + peers_seen["debits_undue"] + len(failures), device=device,
            checks={"failures": failures, "host_fallbacks": checked["host_fallbacks"],
                    "hash_host_fallbacks": checked["hash_host_fallbacks"], "compared": compared},
            breakdown=breakdown, notes=notes,
        )
        # how many peers served and how many the node ended with go into the
        # result line, and where the set changes the table cache's four
        # events, before `compared`
        obs["line_extras"] = {"peers": peers_seen["ended"]}
        if "valset" in mix:
            obs["line_extras"]["table_cache_events"] = table_events
        return obs
    except RunFailure as e:
        print(f"benchmark: {e}", file=sys.stderr)
        raise SystemExit(2) from None
    finally:
        if watch is not None:
            watch.stop()
        if node is not None:
            try:
                node.stop()
            except Exception as e:  # noqa: BLE001 - teardown must reach the children
                print(f"benchmark: node.stop(): {type(e).__name__}: {e}", file=sys.stderr)
        for child in children:
            if child.name == "client" and child.proc.poll() is None:
                try:
                    child.proc.stdin.write("quit\n")
                    child.proc.stdin.flush()
                except OSError:
                    pass
            child.stop()
        shutil.rmtree(work, ignore_errors=True)
