from benchmark.lib import fastsync_counters


def reduce(obs):
    got = fastsync_counters.per_block(obs, "tendermint_hash_seconds_sum")
    return None if got is None else 1e3 * got
