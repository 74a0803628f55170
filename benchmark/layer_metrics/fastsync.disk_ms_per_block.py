from benchmark.lib import own_work


def reduce(obs):
    return own_work.disk_ms_per_block(obs)
