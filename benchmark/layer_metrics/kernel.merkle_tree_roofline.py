from benchmark.lib import chain, counts, ledger


def reduce(obs):
    got = ledger.traced_trees(obs)
    if got is None:
        return None
    seconds, runs, leaves = got
    # every block of a mix holds txs of the same sizes but for a digit or
    # two of the value: the block the window opened on stands for all
    sizes = [len(tx) for tx in chain.block_txs(obs["mix"], int(obs["heights"][0]) + 1)]
    if len(sizes) != leaves:
        return None
    least_s = runs * counts.merkle_tree_bytes(sizes) / counts.peaks(obs["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
