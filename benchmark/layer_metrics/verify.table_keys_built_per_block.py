from benchmark.lib import fastsync_counters


def reduce(obs):
    return fastsync_counters.per_block(obs, "tendermint_verify_table_keys_built_total")
