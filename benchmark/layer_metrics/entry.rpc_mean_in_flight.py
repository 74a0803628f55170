from benchmark.lib import own_work


def reduce(obs):
    return own_work.mean_in_flight(obs)
