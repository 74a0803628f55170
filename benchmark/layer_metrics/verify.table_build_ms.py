from benchmark.lib import own_work


def reduce(obs):
    return own_work.table_build_ms(obs)
