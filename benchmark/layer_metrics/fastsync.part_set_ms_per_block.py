from benchmark.lib import fastsync_stages


def reduce(obs):
    return fastsync_stages.ms_per_block(obs, "part_set")
