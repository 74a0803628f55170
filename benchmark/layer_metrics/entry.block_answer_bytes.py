from benchmark.lib import own_work


def reduce(obs):
    return own_work.bytes_per_read(obs, "block")
