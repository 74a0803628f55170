"""Who serves the chain and what each server answers, as data.

A deployment's file says how many serving peers there are (`"peers"`). A
mix may carry `"peers": {"kind": "<name>", ...}`: a peer rule, found by
its name as a tx rule and a validator-set rule are
(`peer_rules/<kind>.py`, `chain._rule`), whose one function

    answer(rule, peer_index, n_peers, height) -> "sound" | "flip_sig" | "silent"

is pure: seeded by nothing but its arguments and the rule's own numbers.
`sound` sends the generator's bytes; `flip_sig` the block with one
signature bit of its `last_commit` flipped (`chain.tamper`, the lie the
planted-fault check plants at the chain's tail); `silent` nothing (the
pool's 15 s timeout and its 10 kB/s floor are then what is measured). A
mix without `"peers"` is all `sound`.

The serving peer (`lib/peer.py`) makes its answers from `answers()` once,
when it starts; the driver holds the node to `account()`: a debit of a
peer that lied is due, a debit of one that did not is a failure, and so
is a liar the node still keeps. Nothing here starts a JAX backend.
"""

from __future__ import annotations

import json
import os
import re

from . import chain as chainlib

PEER_RULES = os.path.join(chainlib.BENCH, "peer_rules")
ANSWERS = ("sound", "flip_sig", "silent")
# a lying peer's log, a row a lie: `lied flip_sig: height 207 at 1700000000.123`
LIED = re.compile(r"^lied (\w+): height (\d+) at ([0-9.]+)$", re.MULTILINE)
# and what a peer of several, or under a rule, says as it ends: `served: [1, 5, 9, ...]`
SERVED = re.compile(r"^served: (\[.*\])$", re.MULTILINE)


def count(config: dict) -> int:
    """How many peers serve the deployment's chain."""
    n = config.get("peers", 1)
    if type(n) is not int or n < 1:
        raise ValueError(f"a deployment's \"peers\" is an integer of 1 or more, not {n!r}")
    return n


def keeps_notes(mix: dict, n_peers: int) -> bool:
    """A peer of several, or one under a rule, notes what it serves and
    says so at its end; the one sound peer of every listed cell runs the
    reactor it always ran and notes nothing."""
    return n_peers > 1 or "peers" in mix


def key_file(index: int) -> str:
    """A peer's node key in the chain's home: the first keeps the name
    the one peer had."""
    return "peer_key.json" if index == 0 else f"peer_key.{index}.json"


def answers(mix: dict, peer_index: int, n_peers: int, n_blocks: int) -> dict[int, str]:
    """height -> what the peer answers a request for it, for every height
    it does not answer soundly, under the mix's optional `peers` rule."""
    rule = mix.get("peers")
    if rule is None:
        return {}
    answer = chainlib._rule("peer rule", PEER_RULES, rule["kind"], "answer")
    out = {}
    for height in range(1, n_blocks + 1):
        got = answer(rule, peer_index, n_peers, height)
        if got not in ANSWERS:
            raise ValueError(f"peer rule {rule['kind']!r} answers {got!r} at height {height}: not one of {ANSWERS}")
        if got != "sound":
            out[height] = got
    return out


def lies_sent(log_text: str) -> list[tuple[str, int, float]]:
    """(answer, height, wall time) of every unsound answer a peer's log
    says it gave."""
    return [(kind, int(height), float(at)) for kind, height, at in LIED.findall(log_text)]


def heights_served(log_text: str) -> list[int]:
    """The heights a stopped peer's log says it sent the generator's bytes
    for, in the order it sent them (none where it wrote no such row)."""
    m = SERVED.search(log_text)
    return json.loads(m.group(1)) if m else []


def account(
    *, lies: dict, debited: set, debits: int, connected: set, h_close: int,
) -> dict:
    """The node's treatment of its peers against what they did. A peer is
    whatever the caller names it by (the driver: its index among the
    serving peers, and the start of a node id that is none of theirs).

    `lies`: peer -> the unsound answers that serving peer gave
    (`lies_sent`), an entry a serving peer; `debited`: the peers the
    node's scorer holds a score or a ban for; `debits`: what
    `tendermint_p2p_peer_misbehavior_total` reads (every kind);
    `connected`: the peers the node's switch held when the window closed.

    * `undue` (`peers_debited_undue` is how many): debited peers that gave
      no unsound answer (one that served nothing is among them).
    * `kept` (`liars_kept`): peers still connected when the window closed that had
      sent a `flip_sig` for a height the node got past by then (the commit
      of height H - 1 travels in block H: a node that applied H - 1 has
      judged block H). When it was sent does not count: the pool asks for
      heights two hundred ahead, and a lie for a height further on sits
      there unexamined.
    * `debits_undue`, what `failed` counts: the counter is labelled by
      `kind` alone, so where no liar is among the debited every debit is
      undue (a mix without a rule: what `failed` always counted), and
      where one is, an undue debit counts once a peer.
    """
    liars = {peer for peer, sent in lies.items() if sent}
    undue = sorted(debited - liars, key=str)
    kept = sorted(
        (
            peer for peer in liars & connected
            if any(kind == "flip_sig" and height - 1 <= h_close for kind, height, _at in lies[peer])
        ),
        key=str,
    )
    return {
        "debits_undue": len(undue) if debited & liars else debits,
        "debited": sorted(debited, key=str), "undue": undue, "kept": kept, "liars": sorted(liars, key=str),
    }
