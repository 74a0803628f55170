from benchmark.lib import rpc

LEAVES = "tendermint_hash_batch_leaves_sum"


def reduce(obs):
    start, end = obs["metrics_start"], obs["metrics_end"]
    leaves = rpc.rise(start, end, LEAVES)
    if leaves <= 0:
        return None
    on_device = rpc.rise(start, end, LEAVES, backend="device") + rpc.rise(start, end, LEAVES, backend="mesh")
    return 100.0 * on_device / leaves
