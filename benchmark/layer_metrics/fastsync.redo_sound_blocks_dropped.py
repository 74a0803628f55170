from benchmark.lib import redos


def reduce(obs):
    return redos.sound_blocks_dropped(obs)
