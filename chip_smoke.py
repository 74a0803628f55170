#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the served path starts on the chip.

    python chip_smoke.py [--seed N] [--full]

What it does, in order (docs/PLATFORM_NOTES.md, README.md "Running"):

1. Looks for the chip first, in a child, before anything is generated or
   compiled. No accelerator -> exit 3, no result line.
2. Generates, on the host, a seeded 1,024-validator chain of 70 blocks
   (kvstore app, five blocks of 10,000 txs) and a second chain from the
   same seed whose commit for height 40 has one signature bit flipped.
3. Starts two `python -m tendermint_tpu node` children: a serving peer
   held to the CPU whose store holds the chain, and the node under test
   (fresh home, no platform override, nothing injected), which fast-syncs
   from the peer and is the only process that holds the chip. Reads the
   chain back over RPC and requires, from the node's own /health, launch
   ledger and metrics, that the device did the work and no host fallback
   answered where a device answer was due.
4. Starts a second fresh node against the tampered chain: it must refuse
   the commit for height 40 and stop below it, and, compiling the same
   executables against the same cache directory, must report cache hits.
5. In a child of its own (after the nodes released the chip) compiles
   every other kernel the served path can select at its real width and
   compares each with the host bit for bit, one fault planted and
   localised per kernel.

The parent never imports JAX: one process holds a chip at a time. The
last line of standard output is one JSON object with exactly these keys,
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`;
the line before it (`result: {...}`) and `chiprun_out/chip_smoke_report.json`
carry the rest (seconds, `reduced`, failures). Any failed leg is a non-zero
exit.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import random
import re
import shutil
import signal
import statistics
import subprocess
import sys
import tempfile
import time
import traceback
import urllib.error
import urllib.request
from dataclasses import dataclass, field

REPO = os.path.dirname(os.path.abspath(__file__))

# The deployment: BASELINE config 3's 1,000-validator genesis rounded up
# to the fused kernel's 128-validator tile, blocks at the reference's
# default cap of 10,000 txs (types/params.py), kvstore app.
N_VALS = 1024
N_BLOCKS = 70  # the node reaches 69: the last height whose commit rides in a block
BIG_TXS = 10_000
BIG_HEIGHTS = (8, 24, 40, 56, 68)  # one in every 16-commit window
TREE_LEAVES = 8192  # services/hasher.py sends trees this big to the device
TAMPER_HEIGHT = 40
TAMPER_BLOCKS = 48  # the tampered chain stops one window past the fault
CHAIN_ID = "chip-smoke"
GENESIS_TIME = 1_700_000_000_000_000_000

# Exit codes: 0 pass, 1 a leg failed, 3 no accelerator, 4 the script was
# run without the program it drives.
EXIT_FAIL = 1
EXIT_NO_CHIP = 3
EXIT_NO_REPO = 4
# the contract gives 1,200 s; the driver's own start-up eats some of it
DEADLINE_S = 1150.0
# no single wait for a node may eat the whole run: a stalled leg has to
# leave the later legs time to report
SYNC_WAIT_S = 600.0
# the kernels child plans against the deadline less this: a compile that
# takes twice its measured time must not carry the run past the contract
KERNELS_SLACK_S = 150.0

_T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[chip_smoke {time.monotonic() - _T0:7.1f}s] {msg}", flush=True)


def remaining() -> float:
    return DEADLINE_S - (time.monotonic() - _T0)


# -- 1. the chip ---------------------------------------------------------------

_PROBE = (
    "import json, jax; d = jax.devices(); "
    "print(json.dumps({'platform': d[0].platform, 'kind': d[0].device_kind, "
    "'count': len(d)}))"
)


def find_chip() -> dict | None:
    """Ask JAX, in a child that exits before anything else starts, what
    it finds. Returns the device dict, or None when there is no
    accelerator (CPU only, or the backend failed to start)."""
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _PROBE], capture_output=True, text=True, timeout=180.0
        )
    except subprocess.TimeoutExpired:
        print("chip_smoke: the device probe hung", file=sys.stderr)
        return None
    if proc.returncode != 0:
        print(
            "chip_smoke: JAX could not start a backend:\n" + proc.stderr[-2000:],
            file=sys.stderr,
        )
        return None
    device = json.loads(proc.stdout.strip().splitlines()[-1])
    if device["platform"] == "cpu":
        return None
    return device


# -- 2. the chain (host only) --------------------------------------------------


@dataclass
class Chain:
    """A committed chain held in memory: block h is `blocks[h - 1]`, the
    commit that seals it `commits[h - 1]` (and rides in block h + 1),
    `app_hashes[h - 1]` the app hash after applying it."""

    genesis: object
    validators: object  # ValidatorSet, genesis order
    blocks: list = field(default_factory=list)
    commits: list = field(default_factory=list)
    block_ids: list = field(default_factory=list)
    app_hashes: list = field(default_factory=list)
    tampered: tuple | None = None  # (height, validator index)

    @property
    def chain_id(self) -> str:
        return self.genesis.chain_id

    def entries(self, lo: int, hi: int) -> list:
        """(block_id, height, commit) for heights lo..hi inclusive: the
        argument `ValidatorSet.verify_commit_batched` takes."""
        return [
            (self.block_ids[h - 1], h, self.commits[h - 1])
            for h in range(lo, hi + 1)
        ]


def _block_txs(height: int, big_heights, big_txs: int) -> list[bytes]:
    if height in big_heights:
        return [b"k%06d-%03d=v%08x" % (i, height, i * height) for i in range(big_txs)]
    return [b"h%03d-%d=%d" % (height, i, height * 7 + i) for i in range(3)]


def build_chain(
    seed: int,
    n_vals: int = N_VALS,
    n_blocks: int = N_BLOCKS,
    big_heights=BIG_HEIGHTS,
    big_txs: int = BIG_TXS,
    tamper_height: int | None = None,
    home: str | None = None,
) -> Chain:
    """Generate the chain from `seed` with host crypto only (every
    verifier is passed explicitly: a `verifier=None` anywhere below would
    fall through to `default_verifier()` and start a JAX backend in this
    process). With `home`, the blocks and the final state also go into
    that node home's stores, which is what the serving peer starts from.

    `tamper_height` flips one bit of one present signature in the commit
    for that height; the next block is built over the tampered commit, so
    the chain stays self-consistent and only signature verification can
    tell."""
    from tendermint_tpu.abci.apps import KVStoreApp
    from tendermint_tpu.abci.client import local_client_creator
    from tendermint_tpu.blockchain.store import BlockStore
    from tendermint_tpu.config import Config
    from tendermint_tpu.crypto.keys import PrivKey
    from tendermint_tpu.db.kv import MemDB, SQLiteDB
    from tendermint_tpu.services.verifier import HostBatchVerifier
    from tendermint_tpu.state import apply_block, make_genesis_state
    from tendermint_tpu.types import BlockID, Commit, Txs, Validator, ValidatorSet
    from tendermint_tpu.types.block import Block
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.vote import VOTE_TYPE_PRECOMMIT, Vote

    rng = random.Random(seed)
    keys = [
        PrivKey(hashlib.sha256(b"chip-smoke/%d/val/%d" % (seed, i)).digest())
        for i in range(n_vals)
    ]
    by_addr = {k.pub_key.address: k for k in keys}
    valset = ValidatorSet(
        [
            Validator(address=k.pub_key.address, pub_key=k.pub_key, voting_power=10)
            for k in keys
        ]
    )
    privs = [by_addr[v.address] for v in valset.validators]
    genesis = GenesisDoc(
        chain_id=CHAIN_ID,
        genesis_time=GENESIS_TIME,
        validators=[
            GenesisValidator(pub_key=v.pub_key, power=v.voting_power)
            for v in valset.validators
        ],
    )
    if home is not None:
        cfg = Config.default(home)
        state_db, store_db = SQLiteDB(cfg.db_path("state")), SQLiteDB(
            cfg.db_path("blockstore")
        )
    else:
        state_db, store_db = MemDB(), MemDB()
    state = make_genesis_state(state_db, genesis)
    state.save()
    store = BlockStore(store_db)
    conns = local_client_creator(KVStoreApp())()
    host = HostBatchVerifier()
    chain = Chain(genesis=genesis, validators=valset)
    # a handful of validators sit out every commit (absent lanes reach
    # the kernel as masked rows), never enough to cost the quorum
    n_absent = max(1, n_vals // 64)
    for height in range(1, n_blocks + 1):
        block = Block.make_block(
            height=height,
            chain_id=CHAIN_ID,
            txs=Txs(_block_txs(height, big_heights, big_txs)),
            last_commit=chain.commits[-1] if chain.commits else Commit.empty(),
            last_block_id=state.last_block_id,
            time=GENESIS_TIME + height * 1_000_000_000,
            validators_hash=state.validators.hash(),
            app_hash=state.app_hash,
        )
        parts = block.make_part_set()
        block_id = BlockID(block.hash(), parts.header)
        absent = set(rng.sample(range(n_vals), n_absent))
        precommits: list = []
        for i, key in enumerate(privs):
            if i in absent:
                precommits.append(None)
                continue
            vote = Vote(
                validator_address=key.pub_key.address,
                validator_index=i,
                height=height,
                round=0,
                timestamp=GENESIS_TIME + height * 1_000_000_000,
                type=VOTE_TYPE_PRECOMMIT,
                block_id=block_id,
            )
            precommits.append(vote.with_signature(key.sign(vote.sign_bytes(CHAIN_ID))))
        if height == tamper_height:
            # a bit of R (the first 32 bytes): the curve check on the
            # device has to catch it, not the host's S < L precheck
            idx = next(i for i in range(n_vals // 3, n_vals) if i not in absent)
            sig = bytearray(precommits[idx].signature)
            sig[rng.randrange(31)] ^= 1 << rng.randrange(8)
            precommits[idx] = precommits[idx].with_signature(bytes(sig))
            chain.tampered = (height, idx)
        commit = Commit(block_id=block_id, precommits=precommits)
        store.save_block(block, parts, commit)
        apply_block(
            state,
            block,
            parts.header,
            conns.consensus,
            verifier=host,
            commit_preverified=True,
        )
        chain.blocks.append(block)
        chain.commits.append(commit)
        chain.block_ids.append(block_id)
        chain.app_hashes.append(state.app_hash)
    conns.close()
    if home is not None:
        state_db.close()
        store_db.close()
    return chain


def host_reference(chain: Chain, upto: int, window: int = 16) -> dict:
    """The plain reference for the sync leg: the same commit entries
    through `ValidatorSet.verify_commit_batched` with the host library,
    and every block's tx root through merkle/simple.py. Returns the
    heights accepted, the refusals by height, and whether every root
    equals the block header's `data_hash`."""
    from tendermint_tpu.merkle.simple import simple_hash_from_byte_slices
    from tendermint_tpu.services.verifier import HostBatchVerifier
    from tendermint_tpu.types.errors import ValidationError

    host = HostBatchVerifier()
    accepted: list[int] = []
    refused: dict[int, str] = {}
    for lo in range(1, upto + 1, window):
        hi = min(upto, lo + window - 1)
        try:
            chain.validators.verify_commit_batched(
                chain.chain_id, chain.entries(lo, hi), host
            )
            accepted.extend(range(lo, hi + 1))
        except ValidationError:
            # localise: the batched call names one failing entry; the
            # per-height pass says exactly which heights the host refuses
            for h in range(lo, hi + 1):
                try:
                    chain.validators.verify_commit_batched(
                        chain.chain_id, chain.entries(h, h), host
                    )
                    accepted.append(h)
                except ValidationError as e:
                    refused[h] = str(e)
    roots_ok = all(
        simple_hash_from_byte_slices(list(b.data.txs)) == b.header.data_hash
        for b in chain.blocks[:upto]
    )
    return {"accepted": accepted, "refused": refused, "roots_ok": roots_ok}


# -- 3. children ---------------------------------------------------------------


def write_home(home: str, genesis) -> None:
    """config.toml + genesis.json of one node home. Ports are ephemeral
    (the CLI prints the ones it bound), peer exchange is off (two nodes,
    one seed), and the link is LAN-class: at the reference's default
    512 kB/s the 13 MB chain would trickle in three blocks a second and
    no 16-commit window would ever fill."""
    from tendermint_tpu.config import Config, write_config

    os.makedirs(home, exist_ok=True)
    cfg = Config.default(home)
    cfg.base.log_level = "*:info"
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.pex = False
    cfg.p2p.send_rate = cfg.p2p.recv_rate = 100_000_000
    write_config(cfg)
    genesis.save_as(cfg.genesis_path())


_CHILDREN: list["Child"] = []
_DETAIL: dict = {}  # too long for the result line: goes to the report file
_UP = re.compile(r"up: p2p :(\d+) rpc :(\d+)")


class Child:
    """One `python -m tendermint_tpu node` process (or any command),
    its output in a log file, stopped by `stop()` or at exit."""

    def __init__(self, name: str, argv: list[str], env: dict, workdir: str):
        self.name = name
        self.log_path = os.path.join(workdir, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            argv, env=env, cwd=REPO, stdout=self._log, stderr=subprocess.STDOUT
        )
        _CHILDREN.append(self)

    def output(self) -> str:
        with open(self.log_path, "r", errors="replace") as f:
            return f.read()

    def wait_up(self, timeout: float) -> tuple[int, int]:
        """Block until the node prints its ports; (p2p, rpc)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            m = _UP.search(self.output())
            if m:
                return int(m.group(1)), int(m.group(2))
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"{self.name} exited with {self.proc.returncode} before it was up",
                    self,
                )
            time.sleep(0.25)
        raise SmokeFailure(f"{self.name} was not up after {timeout:.0f}s", self)

    def stop(self, grace: float = 30.0) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(grace)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(10)
        if not self._log.closed:
            self._log.close()


def stop_all() -> None:
    for child in _CHILDREN:
        try:
            child.stop(grace=10.0)
        except Exception:  # noqa: BLE001 - teardown must reach every child
            traceback.print_exc()


class SmokeFailure(Exception):
    def __init__(self, msg: str, child: Child | None = None):
        super().__init__(msg)
        self.child = child


def child_env(cpu: bool) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    env["PYTHONUNBUFFERED"] = "1"
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    return env


def start_node(name: str, home: str, workdir: str, cpu: bool, seeds: str = "") -> Child:
    argv = [sys.executable, "-m", "tendermint_tpu", "node", "--home", home]
    if seeds:
        argv += ["--seeds", seeds]
    return Child(name, argv, child_env(cpu), workdir)


# -- RPC reads -----------------------------------------------------------------


def _http_get(url: str, timeout: float = 60.0) -> tuple[int, bytes]:
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:  # /health answers 503 with a body
        return e.code, e.read()


_SERIES = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_metrics(text: str) -> dict:
    """Prometheus text -> {name: [(labels, value), ...]}."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SERIES.match(line)
        if not m:
            continue
        labels = dict(_LABEL.findall(m.group(2) or ""))
        out.setdefault(m.group(1), []).append((labels, float(m.group(3))))
    return out


def metric(metrics: dict, name: str, **labels) -> float:
    """Sum of the series of `name` whose labels include `labels`."""
    return sum(
        v
        for ls, v in metrics.get(name, [])
        if all(ls.get(k) == str(want) for k, want in labels.items())
    )


def observations_above(metrics: dict, name: str, bound: float, **labels) -> float:
    """How many observations of a histogram exceeded `bound` (a bucket
    edge): count minus the cumulative bucket at `le=bound`."""
    at = sum(
        v
        for ls, v in metrics.get(name + "_bucket", [])
        if ls.get("le") not in (None, "+Inf")
        and float(ls["le"]) == bound
        and all(ls.get(k) == str(want) for k, want in labels.items())
    )
    return metric(metrics, name + "_count", **labels) - at


def wait_height(client, child: Child, want: int, timeout: float) -> int:
    """Block until the node holds `want` blocks and has handed over from
    fast-sync to consensus (the flag flips a tick after the last apply)."""
    deadline = time.monotonic() + timeout
    last = -1
    while time.monotonic() < deadline:
        if child.proc.poll() is not None:
            raise SmokeFailure(
                f"{child.name} exited with {child.proc.returncode} while syncing", child
            )
        try:
            sync = client.status()["sync_info"]
            last = sync["latest_block_height"]
            if last >= want and not sync["catching_up"]:
                return last
        except (OSError, ValueError):
            pass
        time.sleep(0.5)
    raise SmokeFailure(
        f"{child.name} reached height {last}, wanted {want} and caught up, "
        f"in {timeout:.0f}s",
        child,
    )


def read_node(client, rpc_port: int, chain: Chain) -> dict:
    """Everything the checks need from one node, over RPC: a few of each
    read a user would make, plus health, the launch ledger and metrics."""
    from tendermint_tpu.types.tx import tx_hash

    base = f"http://127.0.0.1:{rpc_port}"
    status = client.status()
    height = status["sync_info"]["latest_block_height"]
    obs: dict = {"status": status, "height": height}
    hashes: dict[int, str] = {}
    top = height
    while top >= 1:
        page = client.blockchain(min_height=max(1, top - 19), max_height=top)
        for meta in page["block_metas"]:
            hashes[meta["height"]] = meta["hash"]
        top -= 20
    obs["block_hashes"] = hashes
    big = [h for h in BIG_HEIGHTS if h <= height and len(chain.blocks[h - 1].data.txs) > 3]
    sample = sorted({1, 16, 17, 33, 49, 65, height, *big} & set(range(1, height + 1)))
    obs["blocks"] = {h: client.block(h)["block"] for h in sample}
    obs["commits"] = {h: client.commit(h) for h in sample[:4]}
    obs["validators"] = client.validators()
    reads = []
    for h in big[:2]:
        txs = chain.blocks[h - 1].data.txs
        for i in (0, len(txs) // 2, len(txs) - 1):
            raw = bytes(txs[i])
            key = raw.split(b"=", 1)[0]
            reads.append(
                {
                    "height": h,
                    "index": i,
                    "raw": raw.hex(),
                    "tx": client.tx(tx_hash(raw), prove=True),
                    "query": client.abci_query(data=key),
                }
            )
    obs["tx_reads"] = reads
    code, body = _http_get(base + "/health")
    obs["health"] = json.loads(body)
    obs["health_http"] = code
    _, body = _http_get(base + "/dump_telemetry?launches=1024&spans=1")
    dump = json.loads(body)["result"]
    obs["launches"] = (dump.get("launches") or {}).get("records", [])
    obs["breakers"] = dump.get("breakers", {})
    code, body = _http_get(base + "/metrics")
    obs["metrics"] = parse_metrics(body.decode())
    return obs


# -- the checks (pure: observations in, failures out) -------------------------


def ledger_coverage(records: list[dict]) -> dict:
    """What the launch ledger says about the fast-sync windows (the
    records the reactor tagged with `height_lo`/`height_hi`): heights a
    device launch covered, heights something else answered, and the
    distinct (K padded, K real, path) shapes that were launched."""
    device, other = set(), set()
    shapes: dict[tuple, int] = {}
    widths = set()
    for r in records:
        lo, hi = r.get("height_lo"), r.get("height_hi")
        if lo is None or hi is None:
            continue
        heights = range(int(lo), int(hi) + 1)
        if r.get("error") or r.get("backend") not in ("tables", "mesh"):
            other.update(heights)
            continue
        device.update(heights)
        k = len(heights)
        n = max(1, int(r.get("rows", 0)) // k)
        k_padded = (int(r.get("rows", 0)) + int(r.get("rows_padded", 0))) // n
        path = "fused" if k >= 8 else "materialized"
        shapes[(k_padded, n, path)] = shapes.get((k_padded, n, path), 0) + 1
        widths.add(int(r.get("mesh_width", 1)))
    return {
        "device_heights": device,
        "other_heights": other,
        "shapes": shapes,
        "mesh_widths": widths,
    }


def check_identity(obs: dict, chain: Chain, want_height: int) -> list[str]:
    """The node holds the source chain: height, every block hash, the
    app hash, and what the sampled reads returned."""
    from tendermint_tpu.merkle.simple import simple_hash_from_byte_slices

    bad: list[str] = []
    height = obs["height"]
    if height < want_height:
        bad.append(f"height {height} < {want_height}")
        return bad
    for h in range(1, height + 1):
        want = chain.block_ids[h - 1].hash.hex()
        if obs["block_hashes"].get(h) != want:
            bad.append(f"block {h}: hash {obs['block_hashes'].get(h)} != {want}")
    sync = obs["status"]["sync_info"]
    if sync["latest_block_hash"] != chain.block_ids[height - 1].hash.hex():
        bad.append("status: latest_block_hash differs from the source chain")
    if sync["latest_app_hash"] != chain.app_hashes[height - 1].hex():
        bad.append("status: latest_app_hash differs from the source chain")
    if sync["catching_up"]:
        bad.append("status: still catching_up at the tip")
    for h, blk in obs["blocks"].items():
        src = chain.blocks[int(h) - 1]
        if blk["header"]["hash"] != src.hash().hex():
            bad.append(f"block {h}: served header hash differs")
        if blk["header"]["data_hash"] != src.header.data_hash.hex():
            bad.append(f"block {h}: served data_hash differs")
        if blk["txs"] != [bytes(t).hex() for t in src.data.txs]:
            bad.append(f"block {h}: served txs differ")
        # the reference root, recomputed here from what the node served
        root = simple_hash_from_byte_slices([bytes.fromhex(t) for t in blk["txs"]])
        if root.hex() != blk["header"]["data_hash"]:
            bad.append(f"block {h}: merkle/simple.py root != served data_hash")
    for h, res in obs["commits"].items():
        src = chain.commits[int(h) - 1]
        got = res["commit"]
        if got["block_id"]["hash"] != src.block_id.hash.hex():
            bad.append(f"commit {h}: block id differs")
        want_sigs = [v.signature.hex() for v in src.precommits if v is not None]
        if [p["signature"] for p in got["precommits"] if p] != want_sigs:
            bad.append(f"commit {h}: signatures differ")
    want_vals = [v.pub_key.data.hex() for v in chain.validators.validators]
    if [v["pub_key"] for v in obs["validators"]["validators"]] != want_vals:
        bad.append("validators: served set differs from genesis")
    for rd in obs["tx_reads"]:
        where = f"tx {rd['height']}/{rd['index']}"
        raw = bytes.fromhex(rd["raw"])
        if (rd["tx"]["height"], rd["tx"]["index"], rd["tx"]["tx"]) != (
            rd["height"], rd["index"], rd["raw"],
        ):
            bad.append(f"{where}: tx read returned another tx")
        src_root = chain.blocks[rd["height"] - 1].header.data_hash.hex()
        if rd["tx"].get("proof", {}).get("root_hash") != src_root:
            bad.append(f"{where}: proof root differs from the block's data_hash")
        if bytes.fromhex(rd["query"]["value"]) != raw.split(b"=", 1)[1]:
            bad.append(f"{where}: committed write not read back by abci_query")
    return bad


def check_device_work(obs: dict, chain: Chain, device: dict, upto: int) -> list[str]:
    """The chip did the work: the node says which platform it resolved,
    device launches cover every synced height and every big block's
    tree, nothing commit-shaped was answered on the host, every breaker
    is closed and never moved."""
    bad: list[str] = []
    dev = obs["health"].get("device", {})
    got = (dev.get("platform"), dev.get("device_kind"), dev.get("device_count"))
    if got != (device["platform"], device["kind"], device["count"]):
        bad.append(f"health.device says {got}, the probe found {device}")
    if not obs["health"].get("checks", {}).get("breakers", {}).get("ok"):
        bad.append(f"health: breakers check not ok: {obs['health'].get('checks')}")

    cov = ledger_coverage(obs["launches"])
    missing = sorted(set(range(1, upto + 1)) - cov["device_heights"])
    if missing:
        bad.append(f"no device verify launch covers heights {missing}")
    if cov["other_heights"]:
        bad.append(
            f"heights {sorted(cov['other_heights'])} were answered off the device"
        )
    paths = {path for (_kp, _n, path) in cov["shapes"]}
    for want in ("fused", "materialized"):
        if want not in paths:
            bad.append(f"no {want} launch among {sorted(cov['shapes'])}")
    if cov["mesh_widths"] != {device["count"]}:
        bad.append(
            f"ledger mesh_width {sorted(cov['mesh_widths'])} != "
            f"device count {device['count']}"
        )
    big = [
        h for h in range(1, upto + 1) if len(chain.blocks[h - 1].data.txs) >= TREE_LEAVES
    ]
    trees = [
        r
        for r in obs["launches"]
        if r.get("kind") == "hash"
        and r.get("backend") in ("device", "mesh")
        and not r.get("error")
        and int(r.get("rows", 0)) >= TREE_LEAVES
    ]
    if len(trees) < len(big):
        bad.append(f"{len(trees)} device tree launches for {len(big)} big blocks")

    m = obs["metrics"]
    host_lanes = observations_above(m, "tendermint_verify_batch_size", 512.0, backend="host")
    if host_lanes:
        bad.append(f"{host_lanes:.0f} commit-shaped verify calls ran under backend=host")
    host_trees = observations_above(m, "tendermint_hash_batch_leaves", 4096.0, backend="host")
    if host_trees:
        bad.append(f"{host_trees:.0f} trees of >= 8,192 leaves ran under backend=host")
    if metric(m, "tendermint_verify_table_cache_total", event="host_build"):
        bad.append("a comb table was built on the host (event=host_build)")
    for kind in ("verify", "hash", "tables"):
        if metric(m, "tendermint_breaker_state", kind=kind):
            bad.append(f"{kind} breaker is not closed")
        if metric(m, "tendermint_breaker_transitions_total", kind=kind):
            bad.append(f"{kind} breaker moved")
        if metric(m, "tendermint_device_dispatch_failures_total", kind=kind):
            bad.append(f"{kind}: device dispatch failures")
        if metric(m, "tendermint_device_fallback_calls_total", kind=kind):
            bad.append(f"{kind}: calls fell back to the host")
    for name, snap in obs["breakers"].items():
        if snap.get("state", "closed") != "closed" or snap.get("fallback_calls"):
            bad.append(f"{name} snapshot: {snap}")
    if not metric(m, "tendermint_device_primary_calls_total", kind="verify"):
        bad.append("verify: no call was answered by the primary")
    return bad


def check_refusal(obs: dict, chain: Chain, ref: dict) -> list[str]:
    """The tampered chain: the host reference refuses exactly the
    tampered height, and so did the node: it stopped below it, holding
    the source chain's blocks, and debited the peer for a forged block."""
    bad: list[str] = []
    t_height = chain.tampered[0]
    if sorted(ref["refused"]) != [t_height]:
        bad.append(f"host reference refused {sorted(ref['refused'])}, not [{t_height}]")
    height = obs["height"]
    if height >= t_height:
        bad.append(f"node advanced to {height}, past the tampered commit at {t_height}")
    for h in range(1, height + 1):
        if obs["block_hashes"].get(h) != chain.block_ids[h - 1].hash.hex():
            bad.append(f"block {h}: hash differs from the source chain")
    if not metric(obs["metrics"], "tendermint_p2p_peer_misbehavior_total", kind="forged_block"):
        bad.append("no forged_block misbehavior was debited")
    cov = ledger_coverage(obs["launches"])
    if t_height not in cov["device_heights"]:
        bad.append(f"no device launch covered the tampered height {t_height}")
    if cov["other_heights"]:
        bad.append(f"heights {sorted(cov['other_heights'])} were answered off the device")
    return bad


def compile_report(metrics: dict) -> dict:
    """Compile seconds per jitted function (count of executables, total
    seconds) and persistent-cache hits/misses, as the node reported."""
    funs: dict[str, dict] = {}
    for ls, v in metrics.get("tendermint_xla_compile_seconds_count", []):
        funs.setdefault(ls.get("fun", ""), {})["executables"] = int(v)
    for ls, v in metrics.get("tendermint_xla_compile_seconds_sum", []):
        funs.setdefault(ls.get("fun", ""), {})["seconds"] = round(v, 3)
    return {
        "cache_hits": int(metric(metrics, "tendermint_xla_persistent_cache_events_total", event="hit")),
        "cache_misses": int(metric(metrics, "tendermint_xla_persistent_cache_events_total", event="miss")),
        "compile_s_total": round(sum(f.get("seconds", 0.0) for f in funs.values()), 3),
        # the executables worth a line: everything that took a second
        "by_function": {
            name: f for name, f in sorted(funs.items()) if f.get("seconds", 0.0) >= 1.0
        },
    }


def window_times(records: list[dict]) -> dict:
    """Per launched window size K, the first launch (its `host_prep_s`
    holds the compile: jit compiles inside the launch) apart from the
    later ones, whose medians are the run: `host_prep_s` is lane prep +
    dispatch, `finalize_s` what the consumer still waited for at its
    join. (`in_flight_s` is not run time: a window also waits there for
    the consumer to finish the window before it.)"""
    by_k: dict[int, list[dict]] = {}
    for r in records:
        if r.get("height_lo") is None or r.get("error"):
            continue
        by_k.setdefault(int(r["height_hi"]) - int(r["height_lo"]) + 1, []).append(r)
    out = {}
    for k, recs in sorted(by_k.items()):
        later = recs[1:]
        out[f"K={k}"] = {
            "launches": len(recs),
            "first_host_prep_s": recs[0].get("host_prep_s"),
            "later_median_host_prep_s": (
                statistics.median([float(r.get("host_prep_s", 0.0)) for r in later]) if later else None
            ),
            "later_median_finalize_s": (
                statistics.median([float(r.get("finalize_s", 0.0)) for r in later]) if later else None
            ),
        }
    return out


# -- 5. the kernels child (imports JAX; runs alone on the chip) ----------------


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _flip(sig: bytes, byte: int) -> bytes:
    """One bit of one byte of a signature (or any byte string)."""
    return sig[:byte] + bytes([sig[byte] ^ 1]) + sig[byte + 1 :]


def kernels_child(seed: int, budget_s: float, report_path: str) -> int:
    """Compile, at its real width, every kernel the served path can
    select that the sync leg did not reach; compare each with the host
    bit for bit with one fault planted and localised. Checks run in
    order of what their breaking would cost; one whose measured cost
    (`cost_s`, seconds on a cold v5e, my chip run of PR 21) no longer fits
    in `budget_s` is not run and is listed as `reduced`. Writes the
    report as JSON; exit 0 only if every check that ran passed."""
    import numpy as np

    t_start = time.monotonic()

    from tendermint_tpu.utils.jax_cache import enable_persistent_cache

    cache_dir = enable_persistent_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        print("chip_smoke kernels: no accelerator", file=sys.stderr)
        return EXIT_NO_CHIP

    from tendermint_tpu.crypto.keys import PrivKey
    from tendermint_tpu.merkle import simple as host_merkle
    from tendermint_tpu.services.hasher import TreeHasher
    from tendermint_tpu.services.verifier import (
        DeviceBatchVerifier,
        HostBatchVerifier,
        TableBatchVerifier,
    )

    host = HostBatchVerifier()
    checks: list[dict] = []
    reduced: list[dict] = []

    def check(name: str, shape: str, fn, cost_s: float) -> None:
        left = budget_s - (time.monotonic() - t_start)
        if left < 1.25 * cost_s + 5.0:
            reduced.append(
                {
                    "kernel": name,
                    "shape": shape,
                    "reason": f"the 1,200 s contract: {max(left, 0.0):.0f} s were "
                    f"left, a cold run of this check takes ~{cost_s:.0f} s; "
                    "`--full` runs it",
                }
            )
            return
        t0 = time.perf_counter()
        entry = {"kernel": name, "shape": shape}
        try:
            entry.update(fn() or {})
            entry["ok"] = True
        except Exception as e:  # noqa: BLE001 - recorded, and fails the child
            traceback.print_exc()
            entry["ok"] = False
            entry["error"] = f"{type(e).__name__}: {e}"[:1500]
        entry["seconds"] = round(time.perf_counter() - t0, 3)
        checks.append(entry)
        print(f"[kernels] {json.dumps(entry)}", flush=True)

    def expect_equal(got, want, what: str) -> None:
        got, want = np.asarray(got), np.asarray(want)
        if got.shape != want.shape or not np.array_equal(got, want):
            diff = np.argwhere(got != want)[:8].tolist() if got.shape == want.shape else "shape"
            raise AssertionError(f"{what}: device != host at {diff}")

    # ---- comb tables at the north-star width --------------------------------
    n_big = 10_240
    keys = [
        PrivKey(hashlib.sha256(b"chip-smoke/%d/big/%d" % (seed, i)).digest())
        for i in range(n_big)
    ]
    pubs = [k.pub_key.data for k in keys]
    svc = TableBatchVerifier()

    def signed_commit(tag: bytes, ks) -> tuple[list, list]:
        msgs = [
            b'{"chain_id":"chip-smoke","vote":{"height":%s,"index":%d}}' % (tag, i)
            for i in range(len(ks))
        ]
        return msgs, [k.sign(m) for k, m in zip(ks, msgs)]

    def host_grid(pubkeys, commits) -> "np.ndarray":
        grid = np.zeros((len(commits), len(pubkeys)), dtype=bool)
        for ci, (msgs, sigs) in enumerate(commits):
            lanes = [i for i in range(len(pubkeys)) if msgs[i] is not None]
            grid[ci, lanes] = host.verify_batch(
                [(pubkeys[i], msgs[i], sigs[i]) for i in lanes]
            )
        return grid

    def build_tables():
        from tendermint_tpu.ops.ed25519_tables import host_build_key_tables

        (tables, key_ok), first_s = _timed(lambda: svc._tables_for(tuple(pubs)))
        np.asarray(tables[0, 0, 0, :4])
        if not bool(np.all(key_ok)):
            raise AssertionError("table build rejected valid keys")
        # bits, not verdicts: columns at the chunk edges against the
        # host's Python-int build
        cols = [0, 2047, 2048, n_big - 1]
        want, _ok = host_build_key_tables([pubs[c] for c in cols])
        got = np.stack([np.asarray(tables[..., c]) for c in cols], axis=-1)
        expect_equal(got, want, "table columns")
        return {"first_s": round(first_s, 3), "chunks": -(-n_big // 2048)}

    check("tables_build", "10,240 keys, 2,048-key chunks", build_tables, 30)

    commit_a = signed_commit(b"7", keys)
    commit_b = signed_commit(b"8", keys)

    def fused_64():
        commits = [
            (list(c[0]), list(c[1])) for c in [commit_a, commit_b] * 32
        ]
        # planted: a flipped R bit, a flipped S bit, a signature over
        # another lane's message, and an absent vote
        commits[37][1][7777] = _flip(commits[37][1][7777], 3)
        commits[63][1][n_big - 1] = _flip(commits[63][1][n_big - 1], 40)
        commits[5][0][0] = commits[5][0][1]
        commits[12][0][4000] = commits[12][1][4000] = None
        base = host_grid(pubs, [commit_a, commit_b])
        want = np.tile(base, (32, 1))
        for ci, lane in ((37, 7777), (63, n_big - 1), (5, 0), (12, 4000)):
            want[ci, lane] = (
                commits[ci][0][lane] is not None
                and host.verify_batch(
                    [(pubs[lane], commits[ci][0][lane], commits[ci][1][lane])]
                )[0]
            )
        if want[37, 7777] or want[63, n_big - 1] or want[5, 0] or want[12, 4000]:
            raise AssertionError("host accepted a planted fault")
        got, first_s = _timed(lambda: svc.verify_commits(pubs, commits))
        expect_equal(got, want, "fused 10,240 x 64 verdicts")
        _, warm_s = _timed(lambda: svc.verify_commits(pubs, commits))
        return {
            "first_s": round(first_s, 3),
            "warm_s": round(warm_s, 3),
            "lanes": 64 * n_big,
            "planted": [[37, 7777], [63, n_big - 1], [5, 0], [12, 4000]],
        }

    check("tables_fused", "N=10,240 x K=64 (1.25 GB table)", fused_64, 72)

    def chain_1(n: int):
        def run():
            from tendermint_tpu.ops.ed25519_tables import (
                prepare_commit_lanes,
                verify_tables_kernel,
            )

            ks, ps = keys[:n], pubs[:n]
            msgs, sigs = signed_commit(b"9", ks)
            sigs[n // 2] = _flip(sigs[n // 2], 9)
            want = host_grid(ps, [(msgs, sigs)])
            if want[0, n // 2] or int(want.sum()) != n - 1:
                raise AssertionError("host reference: planted fault not localised")
            got, first_s = _timed(lambda: svc.verify_commits(ps, [(msgs, sigs)]))
            expect_equal(got, want, f"K=1 chain at {n} verdicts")
            # the round trip of one K=1 commit verify, inputs on the
            # device, apart from host prep: many readings, the median
            tables, _ok = svc._tables_for(tuple(ps))
            (s, h, r, _pre), prep_s = _timed(
                lambda: prepare_commit_lanes(ps, [(msgs, sigs)])
            )
            s_d, h_d, r_d = (jax.device_put(a) for a in (s, h, r))
            np.asarray(verify_tables_kernel(tables, s_d, h_d, r_d))
            trips = []
            for _ in range(15):
                _, dt = _timed(
                    lambda: np.asarray(verify_tables_kernel(tables, s_d, h_d, r_d))
                )
                trips.append(dt)
            return {
                "first_s": round(first_s, 3),
                "host_prep_s": round(prep_s, 5),
                "round_trip_s": {
                    "median": statistics.median(trips),
                    "min": min(trips),
                    "max": max(trips),
                    "readings": len(trips),
                },
            }

        return run

    check("tables_chain_k1", "N=10,240 x K=1", chain_1(n_big), 61)

    def incremental(n_new: int):
        def run():
            new = [
                PrivKey(hashlib.sha256(b"chip-smoke/%d/new%d/%d" % (seed, n_new, i)).digest())
                for i in range(n_new)
            ]
            step = n_big // n_new
            ks2 = list(keys)
            for j, k in enumerate(new):
                ks2[j * step + 1] = k
            pubs2 = [k.pub_key.data for k in ks2]
            before = svc._build_breaker.snapshot()["total_failures"]
            (_t, key_ok), build_s = _timed(lambda: svc._tables_for(tuple(pubs2)))
            if svc._build_breaker.snapshot()["total_failures"] != before:
                raise AssertionError("the table build faulted (see the log)")
            if not bool(np.all(key_ok)):
                raise AssertionError("incremental build rejected valid keys")
            msgs, sigs = signed_commit(b"10", ks2)
            bad_lane = (n_new // 2) * step + 1  # a lane of a NEW key
            sigs[bad_lane] = _flip(sigs[bad_lane], 17)
            want = host_grid(pubs2, [(msgs, sigs)])
            if want[0, bad_lane] or int(want.sum()) != n_big - 1:
                raise AssertionError("host reference: planted fault not localised")
            got = svc.verify_commits(pubs2, [(msgs, sigs)])
            expect_equal(got, want, f"verdicts after a {n_new}-key change")
            return {"build_s": round(build_s, 3), "new_keys": n_new, "bad_lane": bad_lane}

        return run

    check("tables_incremental", "1 key of 10,240 changed", incremental(1), 7)
    check("tables_incremental", "500 keys of 10,240 changed", incremental(500), 8)

    # ---- generic ladder: Pallas at its narrowest and widest tile, XLA scan ---
    base_n = 4096
    base = []
    for i in range(base_n):
        m = b'{"chain_id":"chip-smoke","tx":%d}' % i
        base.append((pubs[i], m, keys[i].sign(m)))
    base_ok = host.verify_batch(base)

    def ladder(n: int, what: str):
        def run():
            from tendermint_tpu.ops.ed25519_kernel import bucket_size
            from tendermint_tpu.ops.ed25519_ladder_pallas import (
                _tile_lanes,
                use_pallas_ladder,
            )

            triples = [base[i % base_n] for i in range(n)]
            want = np.array([base_ok[i % base_n] for i in range(n)], dtype=bool)
            planted = sorted({1, n // 3, n - 2})
            for j, lane in enumerate(planted):
                pk, m, sig = triples[lane]
                triples[lane] = (
                    (pk, m, _flip(sig, 5)) if j == 0
                    else (pk, m, _flip(sig, 45)) if j == 1
                    else (pk, m + b"!", sig)
                )
                want[lane] = host.verify_batch([triples[lane]])[0]
            if want[planted].any():
                raise AssertionError("host accepted a planted fault")
            size = bucket_size(n)
            pallas = use_pallas_ladder(size)
            if pallas != (what == "pallas"):
                raise AssertionError(f"selection: bucket {size} took pallas={pallas}")
            dev = DeviceBatchVerifier()
            got, first_s = _timed(lambda: dev.verify_batch(triples))
            expect_equal(got, want, f"ladder verdicts at {n}")
            _, warm_s = _timed(lambda: dev.verify_batch(triples))
            return {
                "first_s": round(first_s, 3),
                "warm_s": round(warm_s, 3),
                "bucket": size,
                "tile_lanes": _tile_lanes(size) if pallas else None,
                "planted": planted,
            }

        return run


    # ---- Merkle: one tree, a forest, leaf hashes; SHA-256 and RIPEMD-160 ----
    n_leaves = 65_536
    items = [hashlib.sha256(b"leaf/%d/%d" % (seed, i)).digest() * 2 for i in range(n_leaves)]
    altered = list(items)
    altered[40_001] = b"\x00" + altered[40_001][1:]

    def tree(algo: str):
        def run():
            dev = TreeHasher(backend="device", algo=algo)
            got, first_s = _timed(lambda: dev.root_from_items(items))
            want = host_merkle.simple_hash_from_byte_slices(items, algo)
            if got != want:
                raise AssertionError(f"{algo} root: device {got.hex()} != host {want.hex()}")
            got2, warm_s = _timed(lambda: dev.root_from_items(altered))
            want2 = host_merkle.simple_hash_from_byte_slices(altered, algo)
            if got2 != want2 or got2 == got:
                raise AssertionError(f"{algo} root of the altered leaves differs from host")
            return {"first_s": round(first_s, 3), "warm_s": round(warm_s, 3)}

        return run

    def forest(algo: str):
        def run():
            from tendermint_tpu.ops.merkle_kernel import merkle_roots_forest

            trees = [items, altered, items[:40_000], items]
            got, first_s = _timed(lambda: merkle_roots_forest(trees, algo))
            want = [host_merkle.simple_hash_from_byte_slices(t, algo) for t in trees]
            if got != want:
                wrong = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
                raise AssertionError(f"{algo} forest: trees {wrong} differ from host")
            if got[0] != got[3] or got[1] == got[0]:
                raise AssertionError("altered leaf not localised to its tree")
            _, warm_s = _timed(lambda: merkle_roots_forest(trees, algo))
            return {"first_s": round(first_s, 3), "warm_s": round(warm_s, 3), "trees": 4}

        return run

    def leaves(algo: str):
        def run():
            from tendermint_tpu.ops.merkle_kernel import leaf_hashes_device

            got, first_s = _timed(lambda: leaf_hashes_device(altered, algo))
            want = [host_merkle.leaf_hash(x, algo) for x in altered]
            if got != want:
                wrong = [i for i, (g, w) in enumerate(zip(got, want)) if g != w][:8]
                raise AssertionError(f"{algo} leaf hashes differ from host at {wrong}")
            clean = [host_merkle.leaf_hash(x, algo) for x in items]
            if [i for i in range(n_leaves) if got[i] != clean[i]] != [40_001]:
                raise AssertionError("altered leaf not localised")
            return {"first_s": round(first_s, 3)}

        return run

    def tree_of_hashes():
        dev = TreeHasher(backend="device")
        hashes = [hashlib.sha256(x).digest() for x in items[:10_240]]
        got, first_s = _timed(lambda: dev.root_from_hashes(hashes))
        want = host_merkle.simple_hash_from_hashes(hashes)
        if got != want:
            raise AssertionError("root_from_hashes: device != host")
        return {"first_s": round(first_s, 3)}

    check("merkle_root_device", "65,536 leaves, sha256", tree("sha256"), 10)
    check("merkle_roots_forest", "4 trees <= 65,536 leaves, sha256", forest("sha256"), 8)
    check("leaf_hashes_device", "65,536 leaves, sha256", leaves("sha256"), 1)
    svc._tables.clear()  # ~4 GB of cached tables, done with
    check("ladder_pallas", "65,536 lanes (tile 4,096)", ladder(65_536, "pallas"), 98)
    check("ladder_pallas", "1,024 lanes (tile 1,024)", ladder(1024, "pallas"), 67)
    check("ladder_xla_scan", "512 lanes", ladder(512, "scan"), 50)
    check("merkle_root_device", "65,536 leaves, ripemd160", tree("ripemd160"), 17)
    check("merkle_roots_forest", "4 trees <= 65,536 leaves, ripemd160", forest("ripemd160"), 12)
    check("leaf_hashes_device", "65,536 leaves, ripemd160", leaves("ripemd160"), 2)
    check("tables_chain_k1", "N=1,024 x K=1", chain_1(1024), 41)
    check("merkle_root_from_leaf_words", "10,240 hashes, sha256", tree_of_hashes, 8)

    report = {
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
        "cache_dir": cache_dir,
        "checks": checks,
        "reduced": reduced,
        "ok": all(c["ok"] for c in checks),
    }
    with open(report_path, "w") as f:
        json.dump(report, f, indent=1)
    return 0 if report["ok"] else EXIT_FAIL


# -- the run -------------------------------------------------------------------


def sync_leg(seed: int, device: dict, workdir: str, result: dict) -> list[str]:
    """Legs 2-3: the clean chain, served by a CPU peer, fast-synced by
    the node under test; read back and checked."""
    from tendermint_tpu.rpc.client import HTTPClient

    peer_home = os.path.join(workdir, "peer")
    chain = build_chain(seed, home=peer_home)
    write_home(peer_home, chain.genesis)
    log(
        f"chain: {len(chain.blocks)} blocks, {N_VALS} validators, "
        f"{sum(len(b.data.txs) for b in chain.blocks)} txs"
    )
    peer = start_node("peer", peer_home, workdir, cpu=True)
    p2p_port, _ = peer.wait_up(min(300.0, remaining()))
    node_home = os.path.join(workdir, "node")
    write_home(node_home, chain.genesis)
    t_node = time.monotonic()
    node = start_node(
        "node", node_home, workdir, cpu=False, seeds=f"127.0.0.1:{p2p_port}"
    )
    _, rpc_port = node.wait_up(min(300.0, remaining()))
    log(f"node under test up after {time.monotonic() - t_node:.1f}s; syncing")
    failures: list[str] = []
    # the host reference runs here, while the node syncs
    ref = host_reference(chain, N_BLOCKS - 1)
    if ref["accepted"] != list(range(1, N_BLOCKS)) or not ref["roots_ok"]:
        failures.append(f"host reference refuses the clean chain: {ref['refused']}")
    client = HTTPClient(f"127.0.0.1:{rpc_port}")
    height = wait_height(client, node, N_BLOCKS - 1, min(SYNC_WAIT_S, remaining()))
    sync_s = time.monotonic() - t_node
    log(f"node reached height {height} {sync_s:.1f}s after its start")
    obs = read_node(client, rpc_port, chain)
    node.stop()
    peer.stop()
    failures += check_identity(obs, chain, N_BLOCKS - 1)
    failures += check_device_work(obs, chain, device, N_BLOCKS - 1)
    cov = ledger_coverage(obs["launches"])
    result["sync"] = {
        "height": obs["height"],
        "seconds_from_start": round(sync_s, 1),
        "node_device": obs["health"].get("device"),
        "launch_shapes_K_N_path_count": sorted(
            [list(k) + [n] for k, n in cov["shapes"].items()]
        ),
        "window_times": window_times(obs["launches"]),
        "compile": compile_report(obs["metrics"]),
    }
    log(f"sync leg: {json.dumps(result['sync'])}")
    _DETAIL["sync_launches"] = obs["launches"]
    return [f"sync: {m}" for m in failures]


def tampered_leg(seed: int, workdir: str, result: dict) -> list[str]:
    """Leg 4: a second fresh node against the tampered chain and the
    same cache directory."""
    from tendermint_tpu.rpc.client import HTTPClient

    bad_home = os.path.join(workdir, "peer_tampered")
    chain = build_chain(
        seed, n_blocks=TAMPER_BLOCKS, tamper_height=TAMPER_HEIGHT, home=bad_home
    )
    write_home(bad_home, chain.genesis)
    log(
        f"tampered chain: one bit of validator {chain.tampered[1]}'s signature "
        f"in the commit for height {chain.tampered[0]}"
    )
    peer = start_node("peer_tampered", bad_home, workdir, cpu=True)
    p2p_port, _ = peer.wait_up(min(300.0, remaining()))
    node_home = os.path.join(workdir, "node_tampered")
    write_home(node_home, chain.genesis)
    t_node = time.monotonic()
    node = start_node(
        "node_tampered", node_home, workdir, cpu=False, seeds=f"127.0.0.1:{p2p_port}"
    )
    _, rpc_port = node.wait_up(min(300.0, remaining()))
    ref = host_reference(chain, TAMPER_BLOCKS - 1)
    client = HTTPClient(f"127.0.0.1:{rpc_port}")
    wait_refusal(client, rpc_port, node, min(SYNC_WAIT_S, remaining()))
    obs = read_node(client, rpc_port, chain)
    node.stop()
    peer.stop()
    failures = check_refusal(obs, chain, ref)
    comp = compile_report(obs["metrics"])
    if comp["cache_hits"] < 1:
        failures.append(f"the second start reported no compile-cache hits: {comp}")
    result["tampered"] = {
        "tampered_height_validator": list(chain.tampered),
        "host_refused": sorted(ref["refused"]),
        "node_height": obs["height"],
        "seconds_from_start": round(time.monotonic() - t_node, 1),
        "compile": comp,
    }
    log(f"tampered leg: {json.dumps(result['tampered'])}")
    _DETAIL["tampered_launches"] = obs["launches"]
    return [f"tampered: {m}" for m in failures]


def kernels_leg(seed: int, workdir: str, result: dict) -> list[str]:
    """Leg 5: the kernels child, alone on the chip, with what is left of
    the run's time as its budget."""
    report_path = os.path.join(workdir, "kernels.json")
    budget = max(0.0, remaining() - KERNELS_SLACK_S)
    argv = [
        sys.executable, os.path.abspath(__file__), "--child", "kernels",
        "--seed", str(seed), "--report", report_path, "--budget", f"{budget:.0f}",
    ]
    log(f"kernels child: {budget:.0f}s budget")
    kern = Child("kernels", argv, child_env(cpu=False), workdir)
    try:
        rc = kern.proc.wait(max(30.0, remaining()))
    except subprocess.TimeoutExpired:
        raise SmokeFailure("the kernels child ran out of time", kern) from None
    finally:
        kern.stop()
        for line in kern.output().splitlines():
            if line.startswith("[kernels]"):
                print(line, flush=True)
    if not os.path.exists(report_path):
        raise SmokeFailure(f"the kernels child exited {rc} without a report", kern)
    with open(report_path) as f:
        report = json.load(f)
    result["kernels"] = report["checks"]
    result["reduced"] += report["reduced"]
    failures = [
        f"kernel {c['kernel']} [{c['shape']}]: {c.get('error')}"
        for c in report["checks"]
        if not c["ok"]
    ]
    if rc != 0 and not failures:
        failures.append(f"the kernels child exited {rc}")
    return failures


def run(seed: int) -> int:
    if not os.path.isfile(os.path.join(REPO, "tendermint_tpu", "__main__.py")):
        print(
            f"chip_smoke: the tendermint_tpu package is not next to this script ({REPO}); "
            "nothing was run",
            file=sys.stderr,
        )
        return EXIT_NO_REPO
    device = find_chip()
    if device is None:
        print(
            "chip_smoke: no chip found (JAX sees no accelerator); nothing was run",
            file=sys.stderr,
        )
        return EXIT_NO_CHIP
    log(f"device: {device}")
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    result: dict = {"ok": False, "device": device, "seed": seed, "reduced": []}
    legs = [
        lambda: sync_leg(seed, device, workdir, result),
        lambda: tampered_leg(seed, workdir, result),
    ]
    if device["count"] > 1:
        result["reduced"].append(
            {
                "kernel": "kernels leg",
                "reason": "single-device kernels: checked on a one-chip host; "
                "a multi-chip host runs the mesh through the sync leg",
            }
        )
    else:
        legs.append(lambda: kernels_leg(seed, workdir, result))
    failures: list[str] = []
    try:
        for leg in legs:
            # a failed leg fails the run; the later legs still run, so
            # one run reports everything that is wrong
            try:
                failures += leg()
            except SmokeFailure as e:
                failures.append(str(e))
                if e.child is not None:
                    print(
                        f"---- tail of {e.child.log_path} ----\n"
                        f"{e.child.output()[-6000:]}",
                        file=sys.stderr,
                    )
            finally:
                stop_all()
    finally:
        stop_all()
        result["ok"] = not failures
        result["seconds"] = round(time.monotonic() - _T0, 1)
        if failures:
            result["failures"] = failures
        _save_report(result, workdir)
        shutil.rmtree(workdir, ignore_errors=True)
    for f in failures:
        print(f"chip_smoke: FAILED: {f}", file=sys.stderr)
    log(f"result: {json.dumps(result)}")
    print(last_line(result), flush=True)
    return 0 if result["ok"] else EXIT_FAIL


def last_line(result: dict) -> str:
    """The contract's object and nothing else: exactly `ok` and `device`
    (`platform`, `kind`, `count`). Everything more (seconds, `reduced`,
    failures) is in the `result:` line above it and in the report file."""
    d = result["device"]
    return json.dumps(
        {
            "ok": bool(result["ok"]),
            "device": {
                "platform": str(d["platform"]),
                "kind": str(d["kind"]),
                "count": int(d["count"]),
            },
        }
    )


def wait_refusal(client, rpc_port: int, child: Child, timeout: float) -> None:
    """Block until the node has debited its peer for a forged block (the
    window holding the tampered commit was verified and refused)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if child.proc.poll() is not None:
            raise SmokeFailure(
                f"{child.name} exited with {child.proc.returncode} while syncing", child
            )
        try:
            _, body = _http_get(f"http://127.0.0.1:{rpc_port}/metrics")
            if metric(
                parse_metrics(body.decode()),
                "tendermint_p2p_peer_misbehavior_total",
                kind="forged_block",
            ):
                time.sleep(2.0)  # anything it was going to apply, it has
                return
        except OSError:
            pass
        time.sleep(1.0)
    raise SmokeFailure(
        f"{child.name} refused nothing in {timeout:.0f}s "
        f"(height {client.status()['sync_info']['latest_block_height']})",
        child,
    )


def _save_report(result: dict, workdir: str) -> None:
    """Keep the full result and the children's log tails where a chip
    run's caller finds them (`chiprun_out/` of the checkout)."""
    try:
        out = os.path.join(REPO, "chiprun_out")
        os.makedirs(out, exist_ok=True)
        logs = {
            c.name: c.output()[-20_000:] for c in _CHILDREN if os.path.exists(c.log_path)
        }
        with open(os.path.join(out, "chip_smoke_report.json"), "w") as f:
            json.dump({"result": result, "detail": _DETAIL, "logs": logs}, f, indent=1)
    except OSError:
        traceback.print_exc()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument(
        "--full",
        action="store_true",
        help="lift the 1,200 s contract: run every kernel check, including "
        "those a default run has no time left for (it lists them as `reduced`)",
    )
    ap.add_argument("--child", choices=["kernels"], help=argparse.SUPPRESS)
    ap.add_argument("--report", help=argparse.SUPPRESS)
    ap.add_argument("--budget", type=float, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child == "kernels":
        return kernels_child(args.seed, args.budget, args.report)
    if args.full:
        global DEADLINE_S
        DEADLINE_S = 3300.0  # outside the contract: one chiprun call
    signal.signal(signal.SIGTERM, lambda *_a: (stop_all(), sys.exit(EXIT_FAIL)))
    return run(args.seed)


if __name__ == "__main__":
    sys.exit(main())
