from benchmark.lib import ledger


def reduce(obs):
    recs = ledger.tagged(obs)
    carried = sum(map(ledger.commits, recs))
    if not carried:
        return None
    return 1e3 * sum(float(r.get("host_prep_s", 0.0)) for r in recs) / carried
