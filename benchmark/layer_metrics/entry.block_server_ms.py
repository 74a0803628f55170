from benchmark.lib import own_work


def reduce(obs):
    return own_work.ms_per_read(obs, "block", own_work.TOP_PHASES)
