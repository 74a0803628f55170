"""Tx rule `fixed_keys`: a key space of `keys` K that the chain writes round
and round, `per_block` n txs a block. The block at height h writes the keys
(h*n + i) mod K, i = 0..n-1, each as `k%07d=<h*n + i>`: no two txs of a
chain are the same bytes, every block changes the app's hash, and a
block's last tx is the last write of its key (what
`checks.check_last_write` reads back). At K = n a block writes every key
once; at K < n it wraps, and the later write of a key stands."""


def txs(rule: dict, height: int) -> list[bytes]:
    n, keys = int(rule["per_block"]), int(rule["keys"])
    first = height * n
    return [b"k%07d=%d" % ((first + i) % keys, first + i) for i in range(n)]
