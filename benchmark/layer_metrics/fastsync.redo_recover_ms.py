from benchmark.lib import redos


def reduce(obs):
    return redos.recover_ms(obs)
