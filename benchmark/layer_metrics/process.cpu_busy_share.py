from benchmark.lib import own_work


def reduce(obs):
    return own_work.process_cpu_share(obs)
