SLOW_READ_S = 1.0


def reduce(obs):
    return sum(1 for r in obs["reads"] if r["ok"] and r["end"] - r["due"] > SLOW_READ_S)
