#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data: the cell's entry in
`BENCHMARK.json` names a deployment (`configs/<config>.json`) and a traffic
mix (`traffic/<mix>.json`); `cells/<cell>.json` holds the chain length and
the names of the metrics the cell reports; the mix names its driver
(`drivers/<driver>.py`); every per-layer metric is a file pair under
`layer_metrics/`. Adding any of them needs no edit here.

Lines that carry a number also carry the device. The last line of standard
output is the contract's object; numbers that are not the contract's go on
earlier lines and into `benchmark/out/<cell>-<seed>.json`. Exit codes: 0 a
sound run, 1 a run whose result is not correct, 3 no accelerator (no
result line), 4 the program is not beside the benchmark (no result line).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

T0 = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

EXIT_INCORRECT = 1
EXIT_NO_CHIP = 3
EXIT_NO_REPO = 4


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(path: str):
    name = "benchmark_file_" + os.path.basename(path).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_cell(name: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return {**cell, **load_json("cells", name + ".json")}
    raise SystemExit(f"benchmark: no cell named {name!r} in BENCHMARK.json")


def layer_metrics(names: list[str], obs: dict, log) -> dict:
    """Each named metric's reader over this run's observations. A reader
    that finds nothing to read returns None and the metric is left out."""
    out: dict = {}
    for name in names:
        meta = load_json("layer_metrics", name + ".json")
        reader = load_module(os.path.join(HERE, "layer_metrics", name + ".py"))
        try:
            value = reader.reduce(obs)
        except Exception as e:  # noqa: BLE001 - one reader's fault must not lose the run
            log(f"layer metric {name}: reader failed: {type(e).__name__}: {e}")
            value = None
        if value is not None:
            out[name] = {"value": float(value), "unit": meta["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # for the tests and the on-chip control runs only (benchmark/tests):
    ap.add_argument("--control", default="", help=argparse.SUPPRESS)
    ap.add_argument("--allow-cpu-for-tests", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if not os.path.isfile(os.path.join(ROOT, "tendermint_tpu", "__main__.py")):
        print(
            f"benchmark: the tendermint_tpu package is not beside it ({ROOT}); nothing was run",
            file=sys.stderr,
        )
        return EXIT_NO_REPO
    sys.path.insert(0, ROOT)

    cell = find_cell(args.workload)
    config = load_json("configs", cell["config"] + ".json")
    mix = load_json("traffic", cell["traffic"] + ".json")
    driver = load_module(os.path.join(HERE, "drivers", mix["driver"] + ".py"))

    device_tag = {"text": "device=unknown"}

    def log(msg: str) -> None:
        print(f"[bench {time.monotonic() - T0:7.1f}s {device_tag['text']}] {msg}", flush=True)

    ctx = {
        "t0": T0,
        "root": ROOT,
        "here": HERE,
        "cell": cell,
        "config": config,
        "mix": mix,
        "seed": args.seed,
        "seconds": args.seconds,
        "trace": bool(args.trace),
        "control": args.control,
        "allow_cpu": args.allow_cpu_for_tests,
        "log": log,
        "device_tag": device_tag,
    }
    obs = driver.run(ctx)
    if obs is None:
        return EXIT_NO_CHIP

    if args.trace:
        metrics = layer_metrics(cell["layer_metrics"], obs, log)
    else:
        metrics = {
            name: obs["end_to_end"][name]
            for name in cell["metrics"]
            if name in obs["end_to_end"]
        }
    device = dict(obs["device"])
    line = {
        "correct": bool(obs["correct"]),
        "attempted": int(obs["attempted"]),
        "failed": int(obs["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if args.trace and obs.get("breakdown"):
        line["breakdown"] = obs["breakdown"]
    # what a driver shows beside `compared` (keys the contract does not read)
    line.update(obs.get("line_extras", {}))
    # each number `correct` was decided from beside its limit ([number,
    # limit]; every limit is exact): last in the line, and the last lines
    # of standard error
    line["compared"] = obs["checks"]["compared"]

    out_dir = os.path.join(HERE, "out")
    os.makedirs(out_dir, exist_ok=True)
    detail = {
        "cell": cell["name"], "seed": args.seed, "seconds": args.seconds,
        "trace": bool(args.trace), "control": args.control, "result": line,
        "end_to_end": obs["end_to_end"], "checks": obs["checks"],
        "notes": obs.get("notes", {}),
    }
    with open(os.path.join(out_dir, f"{cell['name']}-{args.seed}.json"), "w") as f:
        json.dump(detail, f, indent=1)
    for name, m in sorted(metrics.items()):
        log(f"metric {name} = {m['value']} {m['unit']}")
    for name, value in obs.get("line_extras", {}).items():
        print(f"{name}: {json.dumps(value)}", file=sys.stderr)
    for name, (number, limit) in line["compared"].items():
        print(f"compared {name}: {number} (limit {limit})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0 if line["correct"] else EXIT_INCORRECT


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 2
    sys.stdout.flush()
    sys.stderr.flush()
    # the node's server and p2p threads are not all daemons: everything
    # the run started has been stopped and joined by now, so leave at once
    os._exit(code)
