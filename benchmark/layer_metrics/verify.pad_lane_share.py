from benchmark.lib import launch_shapes


def reduce(obs):
    recs = launch_shapes.device_launches(obs)
    if recs is None:
        return None
    lanes = sum(int(r["k_launch"]) * int(r["n_launch"]) for r in recs)
    return 100.0 * sum(int(r.get("rows_padded", 0)) for r in recs) / lanes
