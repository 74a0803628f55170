from benchmark.lib import rpc

SECONDS = "tendermint_process_gc_pause_seconds_sum"


def reduce(obs):
    if SECONDS not in obs["metrics_end"]:
        return None
    start, end = obs["window"]
    return 100.0 * rpc.rise(obs["metrics_start"], obs["metrics_end"], SECONDS) / (end - start)
