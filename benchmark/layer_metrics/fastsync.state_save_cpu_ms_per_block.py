from benchmark.lib import own_work


def reduce(obs):
    return own_work.cpu_ms_per_block(obs, ("state_save",))
