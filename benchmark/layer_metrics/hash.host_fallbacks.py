def reduce(obs):
    return obs.get("hash_host_fallbacks")
