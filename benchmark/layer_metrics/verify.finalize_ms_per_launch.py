import statistics

from benchmark.lib import ledger


def reduce(obs):
    recs = ledger.tagged(obs)
    return 1e3 * statistics.median(float(r.get("finalize_s", 0.0)) for r in recs) if recs else None
