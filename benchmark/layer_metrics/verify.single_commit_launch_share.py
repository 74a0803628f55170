from benchmark.lib import launch_shapes


def reduce(obs):
    recs = launch_shapes.device_launches(obs)
    if recs is None:
        return None
    return 100.0 * sum(int(r["k_launch"]) == 1 for r in recs) / len(recs)
