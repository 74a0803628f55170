from benchmark.lib import fastsync_stages


def reduce(obs):
    return fastsync_stages.share_of_window(obs, ("starved",))
