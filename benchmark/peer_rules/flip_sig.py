"""The peer rule `flip_sig`: the serving peers whose indexes are in
`liars` answer with the generator's bytes below `from_height`; from there
they flip one signature bit of the `last_commit` of every `every`-th
height (`from_height`, `from_height + every`, ...) in whatever of those
blocks the node asks them for, until the node drops them. By height and
not by a count of what a peer has sent: the answer is a function of its
arguments alone, so the driver knows every lie there is without asking
the peer (which of them the node asked a liar for, the liar's log says)."""


def answer(rule: dict, peer_index: int, n_peers: int, height: int) -> str:
    start = int(rule["from_height"])
    if peer_index not in rule["liars"] or height < start:
        return "sound"
    return "flip_sig" if (height - start) % int(rule["every"]) == 0 else "sound"
