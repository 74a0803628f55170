from benchmark.lib import redos


def reduce(obs):
    return redos.in_window(obs)
