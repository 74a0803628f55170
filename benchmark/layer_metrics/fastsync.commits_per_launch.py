from benchmark.lib import ledger


def reduce(obs):
    recs = ledger.tagged(obs)
    return sum(map(ledger.commits, recs)) / len(recs) if recs else None
