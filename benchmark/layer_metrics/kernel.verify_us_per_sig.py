from benchmark.lib import ledger


def reduce(obs):
    got = ledger.traced_kernel(obs)
    if got is None:
        return None
    seconds, recs = got
    sigs = sum(int(r.get("rows", 0)) for r in recs)
    return 1e6 * seconds / sigs if sigs else None
