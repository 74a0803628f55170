from benchmark.lib import rpc


def reduce(obs):
    return rpc.rise(obs["metrics_start"], obs["metrics_end"], "tendermint_xla_compile_seconds_count")
