from benchmark.lib import table_cache


def reduce(obs):
    return table_cache.incremental_share(obs)
