from benchmark.lib import own_work


def reduce(obs):
    return own_work.lock_wait_ms_per_block(obs)
