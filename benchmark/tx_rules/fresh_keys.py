"""Tx rule `fresh_keys`: every block writes `per_block` keys that no other
block writes, so the app's key space grows with the chain."""


def txs(rule: dict, height: int) -> list[bytes]:
    n = int(rule["per_block"])
    return [b"h%07d-%d=%d" % (height, i, height * 7 + i) for i in range(n)]
