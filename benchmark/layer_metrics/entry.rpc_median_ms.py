import statistics


def reduce(obs):
    ms = [(r["end"] - r["due"]) * 1e3 for r in obs["reads"] if r["ok"]]
    return statistics.median(ms) if ms else None
