from benchmark.lib import counts, ledger


def reduce(obs):
    got = ledger.traced_kernel(obs)
    if got is None:
        return None
    seconds, recs = got
    # the shape the kernel ran at (PR 27's launch record), pad columns and
    # pad commits with it: the table it reads has `n_launch` columns
    if not all(r.get("k_launch") and r.get("n_launch") for r in recs):
        return None
    moved = sum(counts.verify_launch_bytes(int(r["n_launch"]), int(r["k_launch"])) for r in recs)
    # one chip's share of the bytes against one chip's seconds
    least_s = moved / obs["trace"]["chips"] / counts.peaks(obs["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
