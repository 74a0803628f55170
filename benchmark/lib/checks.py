"""What decides `correct`, outside the window (copies of `chip_smoke.py`'s
checks, cut to what a catch-up run can show).

1. A seeded sample of applied heights, read back over RPC: block hash,
   `data_hash`, `app_hash` and the commit's block id equal the
   generator's record; `reference.py` checks the same sample with
   `hashlib` and the host ed25519 library alone, and per height: the
   header's `validators_hash` is the root of the set `reference.py`
   derives for that height from the genesis set and the mix's changes,
   and the commit verifies against that set. Beside its seeded heights
   the sample holds the four heights around the first and the last change
   of the set the node applied (h - 1, h, h + 1, h + 2 around the first
   header that carries a new hash); the generator's record of the sets is
   compared with the reference's too.
2. The last write of the height the app answers at (`/abci_info`; the
   store and `/status` are a block ahead while that block is executed) is
   read back through `abci_query`.
3. Every verify launch of k*n >= 512 lanes was answered by a device
   backend, no breaker moved, no call fell back to the host; and the
   same of every Merkle tree of 8,192 leaves or more (a block's 10,000
   txs): the hash spine answers it on the device. And the answers that
   were due came (`device_answers_due`): a device verify launch where a
   window carried 512 lanes or more, a device tree where a block held
   8,192 txs or more, and on an accelerator at least one of either kind.
4. The node's `/health` names the device JAX gave this process.
5. The planted fault: the chain's last 16-commit window (heights the node
   never reaches in a run) with one seeded bit of one signature's R
   flipped goes through `ValidatorSet.verify_commit_batched` on the
   process's own verifier and is refused at exactly that height and
   validator; the clean window passes.
6. Every height in the node's store holds the source chain's block
   (`forged_blocks_applied`: all of them, by the store's metas), and, in
   `drivers/catchup.py` over `lib/peers.py` `account`, the node debited
   no peer that gave it no unsound answer and kept no peer that lied to it.

Limits: every comparison is exact (limit 0). Each line printed gives the
number compared beside its limit.
"""

from __future__ import annotations

import random
import re

from . import chain as chainlib
from . import reference, rpc, signer
from .ledger import DEVICE_BACKENDS, HASH_DEVICE_BACKENDS, VERIFY_KINDS

DEVICE_MIN_LANES = 512  # services/verifier.py DEVICE_MIN_BATCH
DEVICE_MIN_LEAVES = 8192  # services/hasher.py DEVICE_MIN_LEAVES
REFUSED = re.compile(r"validator (\d+) \(batch entry (\d+), height (\d+)\)")


def host_fallbacks(launches: list[dict], metrics: dict) -> int:
    """Verify launches of k*n >= 512 lanes a host backend answered (or
    that failed), plus the spine's own fallback and dispatch-failure
    counters."""
    n = 0
    for r in launches:
        if r.get("kind") not in VERIFY_KINDS:
            continue
        lanes = int(r.get("rows", 0)) + int(r.get("rows_cached", 0))
        if lanes >= DEVICE_MIN_LANES and (r.get("error") or r.get("backend") not in DEVICE_BACKENDS):
            n += 1
    n += int(rpc.metric(metrics, "tendermint_device_fallback_calls_total", kind="verify"))
    n += int(rpc.metric(metrics, "tendermint_device_dispatch_failures_total", kind="verify"))
    return n


def hash_host_fallbacks(launches: list[dict], metrics: dict) -> int:
    """Merkle trees of `DEVICE_MIN_LEAVES` leaves or more that a host
    backend answered, plus the hash spine's own fallback and
    dispatch-failure counters. A host tree outside a launch context closes
    no record (`telemetry/launchlog.py` `observe`), so the trees are also
    counted off the histogram of leaves a root, which has a bound at
    8,192 (a host tree of exactly 8,192 leaves is seen by its record
    alone; one seen both ways counts twice, and the limit is 0)."""
    n = 0
    for r in launches:
        if r.get("kind") != "hash" or int(r.get("rows", 0)) < DEVICE_MIN_LEAVES:
            continue
        if r.get("error") or r.get("backend") not in HASH_DEVICE_BACKENDS:
            n += 1
    leaves = "tendermint_hash_batch_leaves"
    under = sum(
        v for ls, v in metrics.get(leaves + "_bucket", [])
        if ls.get("backend") == "host" and ls.get("le") != "+Inf" and float(ls["le"]) == DEVICE_MIN_LEAVES
    )
    n += int(rpc.metric(metrics, leaves + "_count", backend="host") - under)
    n += int(rpc.metric(metrics, "tendermint_device_fallback_calls_total", kind="hash"))
    n += int(rpc.metric(metrics, "tendermint_device_dispatch_failures_total", kind="hash"))
    return n


def sample_heights(seed: int, top: int, n: int) -> list[int]:
    rng = random.Random(seed ^ 0xC0FFEE)
    if top <= n:
        return list(range(1, top + 1))
    return sorted({top, *rng.sample(range(1, top), n - 1)})


def reference_sets(record, config: dict, mix: dict) -> list[dict]:
    """The per-height reference's validator sets for this chain
    (`reference.validator_sets`): the genesis keys and powers, the mix's
    changes with each key rank turned into the seed's key, and the
    addresses the record gives (a record from before `addresses`, which
    the chain cache may still hold, gets the program's)."""
    genesis = [(bytes.fromhex(k), w) for k, w in zip(record.pubkeys, record.powers)]
    address_of = {bytes.fromhex(k): bytes.fromhex(a) for k, a in record.addresses.items()}
    if not address_of:
        from tendermint_tpu.crypto.keys import PubKey

        address_of = {k: PubKey(k).address for k, _w in genesis}
    keys: dict[int, bytes] = {}

    def key(rank: int) -> bytes:
        if rank not in keys:
            keys[rank] = signer.public_bytes(signer.private_key(record.seed, rank))
        return keys[rank]

    changes = {
        h: [(key(rank), power) for rank, power in step]
        for h, step in chainlib.valset_changes(config, mix, record.n_blocks).items()
    }
    return reference.validator_sets(genesis, changes, address_of)


def record_sets_differ(record, sets: list[dict]) -> int:
    """Stretches of the generator's record that are not the reference's."""
    ours = [
        (s["from_height"], s["validators_hash"], s["pubkeys"], s["powers"])
        for s in record.valsets or [record.set_at(1)] if s["from_height"] <= record.n_blocks
    ]
    theirs = [
        (s["from_height"], s["validators_hash"].hex(), [k.hex() for k in s["pubkeys"]], s["powers"])
        for s in sets if s["from_height"] <= record.n_blocks
    ]
    return sum(a != b for a, b in zip(ours, theirs)) + abs(len(ours) - len(theirs))


def boundary_heights(sets: list[dict], top: int) -> list[int]:
    """h - 1 .. h + 2 around the first and the last height h <= `top`
    whose header carries another hash than the header before it."""
    starts = [s["from_height"] for s in sets[1:] if s["from_height"] <= top]
    around = {h + d for h in (starts[:1] + starts[-1:]) for d in (-1, 0, 1, 2)}
    return sorted(h for h in around if 1 <= h <= top)


def check_sample(port: int, record, heights: list[int], sets: list[dict]) -> dict:
    """`sets`: the validator sets the sample is held to, as
    `reference.validator_sets` gives them. Returns the failures in words,
    the fields compared, and how many of the sampled headers carry another
    `validators_hash` than the set of their height and how many of the
    sampled commits do not verify against it."""
    bad: list[str] = []
    compared = hash_differ = commit_differ = 0
    for h in heights:
        held = reference.set_at(sets, h)
        blk = rpc.call(port, f"block?height={h}")["block"]
        hdr = blk["header"]
        want = {
            "hash": record.block_hash[h - 1],
            "data_hash": record.data_hash[h - 1],
            "app_hash": record.app_hash[h - 2] if h >= 2 else "",
            "validators_hash": held["validators_hash"].hex(),
        }
        for field, value in want.items():
            compared += 1
            if hdr[field] != value:
                bad.append(f"block {h}: served {field} {hdr[field][:16]} != source {value[:16]}")
                hash_differ += field == "validators_hash"
        bad += reference.check_block(blk)
        com = rpc.call(port, f"commit?height={h}")["commit"]
        compared += 1
        if com["block_id"]["hash"] != record.block_hash[h - 1]:
            bad.append(f"commit {h}: block id differs from the source chain")
        wrong = reference.check_commit(
            record.chain_id, h, record.block_hash[h - 1], com, held["pubkeys"], held["powers"]
        )
        commit_differ += bool(wrong)
        bad += wrong
    return {"bad": bad, "compared": compared, "hash_differ": hash_differ, "commit_differ": commit_differ}


def check_last_write(port: int, record) -> list[str]:
    """The last write of the height the APP answers at, read back. The
    store, and `/status`, are a block ahead of the app while that block is
    executed, so the height is the app's own (`/abci_info`), before and
    after the query. The node keeps syncing while we ask, the app answers
    from the state its `DeliverTx` calls are writing, and a fixed key
    space is rewritten by every block: any height from the first reading
    to the block in execution after the second may answer."""
    h1 = int(rpc.call(port, "abci_info")["last_block_height"])
    if h1 < 1:
        return ["abci_info: the app says it has applied no block"]
    key, _ = record.last_write[h1 - 1]
    got = rpc.call(port, f"abci_query?data={key}")
    h2 = int(rpc.call(port, "abci_info")["last_block_height"])
    value = got.get("value", "")
    top = min(h2 + 1, record.n_blocks)
    allowed = {record.last_write[h - 1][1] for h in range(h1, top + 1) if record.last_write[h - 1][0] == key}
    if value not in allowed:
        return [f"abci_query: key {bytes.fromhex(key)!r} holds {value!r}, not the write of heights {h1}..{top}"]
    return []


def forged_blocks_applied(store, record) -> list[int]:
    """The heights in the node's store whose block id (the header's hash,
    the part set's count and root: the root covers every byte of the
    block, its `last_commit` with them) is not the source chain's. Every
    height, from the store's metas, and not a sample: a forged block is
    one height."""
    forged = []
    for h in range(1, min(store.height, record.n_blocks) + 1):
        meta = store.load_block_meta(h)
        bid = meta.block_id if meta is not None else None
        if bid is None or (bid.hash.hex(), bid.parts_header.total, bid.parts_header.hash.hex()) != (
            record.block_hash[h - 1], record.parts_total[h - 1], record.parts_hash[h - 1]
        ):
            forged.append(h)
    return forged


def device_answers_due(launches: list[dict], platform: str) -> list[str]:
    """What a run on an accelerator owes of device answers, by the rule
    `verify_host_answers` and `tree_host_answers` follow: a device verify
    launch when some window of the run carried `DEVICE_MIN_LANES` real
    lanes or more (the tagged launch records, host-answered ones with
    them), a device tree when some block held `DEVICE_MIN_LEAVES` txs or
    more, and at least one device answer of either kind: every cell drives
    the device path. Returns what is owed and missing, in words."""
    if platform == "cpu":
        return []
    verify = [r for r in launches if r.get("kind") in VERIFY_KINDS]
    trees = [r for r in launches if r.get("kind") == "hash"]
    by_device = [r for r in verify if not r.get("error") and r.get("backend") in DEVICE_BACKENDS]
    trees_by_device = [r for r in trees if not r.get("error") and r.get("backend") in HASH_DEVICE_BACKENDS]
    owed = []
    if not by_device and any(
        int(r.get("rows", 0)) + int(r.get("rows_cached", 0)) >= DEVICE_MIN_LANES
        for r in verify if r.get("height_lo") is not None
    ):
        owed.append(f"a window carried >= {DEVICE_MIN_LANES} lanes and the launch ledger holds no device verify launch")
    if not trees_by_device and any(int(r.get("rows", 0)) >= DEVICE_MIN_LEAVES for r in trees):
        owed.append(f"a block held >= {DEVICE_MIN_LEAVES} txs and the launch ledger holds no device tree")
    if not by_device and not trees_by_device:
        owed.append("the launch ledger holds no device answer of either kind, verify or tree")
    return owed


def validator_set(record):
    """The validator set of the chain's tail (the heights of
    `record.tail_entries()`) as the program's type, from the record."""
    from tendermint_tpu.crypto.keys import PubKey
    from tendermint_tpu.types import Validator, ValidatorSet

    tail = record.set_at(record.n_blocks - chainlib.FAULT_WINDOW)
    keys = [PubKey(bytes.fromhex(p)) for p in tail["pubkeys"]]
    return ValidatorSet(
        [Validator(address=k.address, pub_key=k, voting_power=w) for k, w in zip(keys, tail["powers"])]
    )


def check_planted_fault(record, seed: int) -> list[str]:
    from tendermint_tpu.services.verifier import default_verifier
    from tendermint_tpu.types.errors import ValidationError

    valset = validator_set(record)
    entries = record.tail_entries()
    rng = random.Random(seed ^ 0xFA17)
    at = rng.randrange(len(entries))
    bid, height, commit = entries[at]
    forged, idx = chainlib.tamper(commit, seed)
    tampered = list(entries)
    tampered[at] = (bid, height, forged)
    verifier = default_verifier()
    bad: list[str] = []
    try:
        valset.verify_commit_batched(record.chain_id, tampered, verifier)
        bad.append(
            f"planted fault: a window with one flipped signature bit (height {height}, "
            f"validator {idx}) was accepted"
        )
    except ValidationError as e:
        m = REFUSED.search(str(e))
        if not m or (int(m.group(1)), int(m.group(3))) != (idx, height):
            bad.append(f"planted fault at height {height}, validator {idx}: refused as {e}")
    try:
        valset.verify_commit_batched(record.chain_id, entries, verifier)
    except ValidationError as e:
        bad.append(f"planted fault: the clean window was refused: {e}")
    return bad


def run_checks(
    *, port, record, config, mix, seed, h_close, launches, metrics, health, devices, log,
    static_reference: bool = False,
) -> dict:
    """Every check; returns the failures in words, the two counts of host
    answers (the readers `verify.host_fallbacks` and `hash.host_fallbacks`
    report them) and each number compared beside its limit. With
    `static_reference` (the control of that name) the sample is held to
    the genesis set at every height."""
    failures: list[str] = []
    compared: dict[str, list] = {}
    sets = reference_sets(record, config, mix)
    differ = record_sets_differ(record, sets)
    around = boundary_heights(sets, h_close)
    log(
        f"check validator sets: {len(sets)} stretches by the reference's set arithmetic, "
        f"{differ} differ from the generator's record (limit 0)"
    )
    compared["record_valsets_differ"] = [differ, 0]
    if differ:
        failures.append(f"{differ} stretches of the generator's validator sets are not the reference's")
    heights = sorted({*sample_heights(seed, h_close, 32), *around})
    got = check_sample(port, record, heights, sets[:1] if static_reference else sets)
    bad = got["bad"]
    log(
        f"check sample: {len(heights)} heights ({len(around)} of them around the first and the last "
        f"set change applied: {around}), {got['compared']} fields and {len(heights)} commits compared, "
        f"{len(bad)} differ (limit 0); validators_hash not the set's of its height: {got['hash_differ']}, "
        f"commits not verified by the set of their height: {got['commit_differ']} (limits 0)"
    )
    compared["sample_differ"] = [len(bad), 0]
    compared["sample_valset_hash_differ"] = [got["hash_differ"], 0]
    compared["sample_commit_differ"] = [got["commit_differ"], 0]
    failures += bad
    bad = check_last_write(port, record)
    log(f"check last write: {len(bad)} differ (limit 0)")
    compared["last_write_differ"] = [len(bad), 0]
    failures += bad
    fallbacks = host_fallbacks(launches, metrics)
    log(f"check device answers: {fallbacks} host answers or faults where a device answer was due (limit 0)")
    compared["verify_host_answers"] = [fallbacks, 0]
    if fallbacks:
        failures.append(f"{fallbacks} verify launches of >= {DEVICE_MIN_LANES} lanes were not answered by the device")
    hash_fallbacks = hash_host_fallbacks(launches, metrics)
    log(f"check device trees: {hash_fallbacks} host answers or faults where a device tree was due (limit 0)")
    compared["tree_host_answers"] = [hash_fallbacks, 0]
    if hash_fallbacks:
        failures.append(f"{hash_fallbacks} Merkle trees of >= {DEVICE_MIN_LEAVES} leaves were not answered by the device")
    owed = device_answers_due(launches, devices[0].platform)
    log(f"check device answers due: {len(owed)} kinds of answer owed and missing (limit 0)")
    compared["device_answers_missing"] = [len(owed), 0]
    failures += owed
    dev = health.get("device", {})
    got = (dev.get("platform"), dev.get("device_kind"), dev.get("device_count"))
    want = (devices[0].platform, devices[0].device_kind, len(devices))
    log(f"check health.device: {got} against {want}")
    compared["health_device_differs"] = [int(got != want), 0]
    if got != want:
        failures.append(f"health.device says {got}, JAX gave this process {want}")
    bad = check_planted_fault(record, seed)
    log(f"check planted fault: {len(bad)} wrong verdicts (limit 0)")
    compared["planted_fault_wrong_verdicts"] = [len(bad), 0]
    failures += bad
    return {
        "failures": failures, "host_fallbacks": fallbacks,
        "hash_host_fallbacks": hash_fallbacks, "compared": compared,
    }
