from benchmark.lib import rpc
from benchmark.lib.table_cache import EVENTS


def reduce(obs):
    start, end = obs["metrics_start"], obs["metrics_end"]
    if not any(labels.get("event") == "joined" for labels, _value in end.get(EVENTS, [])):
        return None  # a program that never joins a build in flight
    joined = rpc.rise(start, end, EVENTS, event="joined")
    built = joined + rpc.rise(start, end, EVENTS, event="miss")
    return 100.0 * joined / built if built > 0 else None
