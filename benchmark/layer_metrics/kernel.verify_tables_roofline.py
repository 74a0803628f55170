from benchmark.lib import counts, ledger


def reduce(obs):
    got = ledger.traced_kernel(obs)
    if got is None:
        return None
    seconds, recs = got
    n = int(obs["config"]["validators"])
    moved = 0
    for r in recs:
        lanes = int(r.get("rows", 0)) + int(r.get("rows_cached", 0)) + int(r.get("rows_padded", 0))
        moved += counts.verify_launch_bytes(n, max(1, round(lanes / n)))
    # one chip's share of the bytes against one chip's seconds
    least_s = moved / obs["trace"]["chips"] / counts.peaks(obs["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
