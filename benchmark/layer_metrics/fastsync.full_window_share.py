from benchmark.lib import fastsync_stages


def reduce(obs):
    return fastsync_stages.full_window_share(obs)
