from benchmark.lib.stats import percentile


def reduce(obs):
    ms = [(r["end"] - r["due"]) * 1e3 for r in obs["reads"] if r["ok"]]
    return percentile(ms, 95) if ms else None
