def reduce(obs):
    return obs["host_fallbacks"]
