from benchmark.lib import own_work


def reduce(obs):
    return own_work.stage_ms_per_block(obs, "index_rows")
