from benchmark.lib import own_work


def reduce(obs):
    return own_work.thread_cpu_share(obs, "p2p_recv")
