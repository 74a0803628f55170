from benchmark.lib import fastsync_stages


def reduce(obs):
    return fastsync_stages.window_cut_share(obs, "boundary")
