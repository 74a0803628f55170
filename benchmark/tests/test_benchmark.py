"""CPU rehearsals of the benchmark. One command runs them all:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

They drive `run.py` as the driver does (a new process per run) on a tiny
deployment, behind the test-only override of the no-chip refusal. Nothing
here yields a device number.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "peers", "compared"}


def bench_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- the contract's form -------------------------------------------------------


def test_benchmark_json_keeps_to_the_contract():
    raw = open(os.path.join(ROOT, "BENCHMARK.json"), "rb").read()
    assert len(raw) <= 64 * 1024
    b = json.loads(raw)
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmark"] and 1 <= b["run_seconds"] <= 51
    assert len(b["command"]) <= 32 and all(1 <= len(w) <= 200 for w in b["command"])
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and PATH.match(c["file"]) and c["file"].startswith("benchmark/")
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        doc = json.load(open(os.path.join(ROOT, c["file"])))
        assert doc["reduced"] == c["reduced"] and all(NAME.match(k) and k in doc for k in c["reduced"])
        assert doc["guarantees"], "a deployment states its guarantees"
    cells = [w["name"] for w in b["workloads"]]
    assert len(set(cells)) == len(cells) and len({(w["config"], w["traffic"]) for w in b["workloads"]}) == len(cells)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        for part in (("cells", w["name"]), ("traffic", w["traffic"])):
            assert os.path.isfile(os.path.join(BENCH, part[0], part[1] + ".json"))
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(1, len(cells) // 2)
    assert {c["name"] for c in b["configs"]} == {w["config"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    names = list(e2e)
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["source"] in SOURCES
        assert m["moves"] in e2e and m["moves"] != "setup_s" and 1 <= len(m["layer"]) <= 200
        assert set(m.get("workloads", cells)) <= set(cells)
        names.append(m["name"])
    assert len(set(names)) == len(names)


def test_every_metric_and_cell_is_a_file_of_its_own():
    b = bench_json()
    e2e = {m["name"] for m in b["end_to_end"]}
    per_layer = {m["name"]: m for m in b["per_layer"]}
    for w in b["workloads"]:
        cell = json.load(open(os.path.join(BENCH, "cells", w["name"] + ".json")))
        assert set(cell["metrics"]) <= e2e and "setup_s" in cell["metrics"] and len(cell["metrics"]) >= 2
        assert cell["layer_metrics"]
        for name in cell["layer_metrics"]:
            assert w["name"] in per_layer[name].get("workloads", [w["name"]])
    for name, m in per_layer.items():
        meta = json.load(open(os.path.join(BENCH, "layer_metrics", name + ".json")))
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert meta[key] == m[key], (name, key)


# -- the yardstick's arithmetic -------------------------------------------------


def test_counts_against_hand_worked_bytes():
    from benchmark.lib import counts

    # the table: 64 windows x 16 entries x 60 limbs x 2 bytes = 122,880 B a
    # validator; a lane: 3 x 32 B in, 1 B out
    assert counts.TABLE_BYTES_PER_VALIDATOR == 122_880
    assert counts.verify_launch_bytes(100, 16) == 100 * 122_880 + 1_600 * 97 == 12_443_200
    assert counts.verify_launch_bytes(1024, 16) == 1024 * 122_880 + 16_384 * 97 == 127_418_368
    assert counts.verify_launch_bytes(1024, 1) == 125_829_120 + 99_328
    assert counts.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")


def test_kernel_metrics_count_only_what_the_device_answered():
    """At 100 validators a window of 5 commits or fewer (under 512 lanes)
    goes to the host library: its signatures and its table read are no
    work of the kernel's, and the kernel's metrics leave them out."""
    import importlib.util

    from benchmark.lib import counts, ledger

    def launch(t, k, backend):
        # the device's windows run at the fused shape, 16 commits x 128 columns
        shape = {"k_launch": 16, "n_launch": 128, "rows_padded": 2048 - 100 * k} if backend == "tables" else {}
        return {"kind": "tables" if backend == "tables" else "verify", "t": t, "backend": backend,
                "height_lo": 10, "height_hi": 10 + k - 1, "rows": 100 * k, **shape}

    obs = {
        "config": {"validators": 100}, "device_kind": "TPU v5 lite",
        "trace": {"wall0": 1000.0, "window_s": 4.0, "chips": 1,
                  "modules": {"jit_verify_tables_kernel(123)": 0.004, "jit_other(7)": 0.5}},
        "launches": [launch(1001.0, 3, "host"), launch(1001.5, 16, "tables"), launch(1002.0, 5, "host"),
                     launch(1003.0, 6, "tables"), launch(1007.0, 16, "tables")],
    }
    seconds, recs = ledger.traced_kernel(obs)
    assert seconds == 0.004 and [ledger.commits(r) for r in recs] == [16, 6]

    def reader(name):
        spec = importlib.util.spec_from_file_location("m", os.path.join(BENCH, "layer_metrics", name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.reduce

    assert reader("kernel.verify_us_per_sig")(obs) == pytest.approx(1e6 * 0.004 / 2_200)
    # the table the kernel reads has the launch's 128 columns, not the set's
    # 100, and a window of 6 commits runs all 16 of the fused shape
    least = 2 * counts.verify_launch_bytes(128, 16) / 819e9
    assert reader("kernel.verify_tables_roofline")(obs) == pytest.approx(100 * least / 0.004)
    # a program whose records carry no launch shape (older than PR 27): nothing to read
    bare = [{k: v for k, v in r.items() if k not in ("k_launch", "n_launch")} for r in obs["launches"]]
    assert reader("kernel.verify_tables_roofline")({**obs, "launches": bare}) is None
    # only host launches inside the stretch: nothing to read
    obs["launches"] = obs["launches"][:1]
    assert ledger.traced_kernel(obs) is None


def ledger_of(n_vals: int, txs: int, device: bool, blocks: int = 40) -> list[dict]:
    """A hand-made launch ledger of a run on an accelerator: windows of 16
    commits of `n_vals` validators (answered by the device where they carry
    512 lanes or more and `device` holds, else by the host library) and, of
    a block of 8,192 txs or more, a tree a block (a smaller one never goes
    through the hash spine's launch records)."""
    recs = []
    for lo in range(1, blocks, 16):
        on_device = device and 16 * n_vals >= 512
        recs.append({"kind": "tables" if on_device else "verify", "backend": "tables" if on_device else "host",
                     "t": 1000.0 + lo, "height_lo": lo, "height_hi": lo + 15, "rows": 16 * n_vals})
    if txs >= 8192:
        recs += [{"kind": "hash", "backend": "device" if device else "host", "t": 1000.0 + h, "rows": txs}
                 for h in range(1, blocks)]
    return recs


@pytest.mark.parametrize(
    "n_vals, txs, device, owed",
    [
        # `local-4` under `full`: no window ever reaches 512 lanes, and its 10,000-leaf trees are the device's
        (4, 10_000, True, []),
        # `local-4` under `sparse` drives no device path at all
        (4, 3, True, ["no device answer of either kind"]),
        # the four accepted cells owe a verify launch, as before
        (100, 3, True, []), (1000, 3, True, []), (1000, 10_000, True, []),
        (1000, 3, False, ["no device verify launch", "no device answer of either kind"]),
        (100, 3, False, ["no device verify launch", "no device answer of either kind"]),
        # 1,000 validators at 10,000 txs and nothing from the device: all three are owed
        (1000, 10_000, False, ["no device verify launch", "no device tree", "no device answer of either kind"]),
    ],
)
def test_a_run_owes_the_device_answers_its_shapes_make_due(n_vals, txs, device, owed):
    from benchmark.lib import checks

    got = checks.device_answers_due(ledger_of(n_vals, txs, device), "tpu")
    assert len(got) == len(owed) and all(want in row for want, row in zip(owed, got)), got
    # the CPU's rehearsals owe nothing: no launch there is the device's to answer
    assert checks.device_answers_due(ledger_of(n_vals, txs, device), "cpu") == []


def test_a_device_answer_is_owed_kind_by_kind():
    from benchmark.lib import checks

    # the trees reached the device and the 16,000-lane windows did not: the verify launch is still owed
    mixed = [r for r in ledger_of(1000, 10_000, True) if r["kind"] == "hash"] + ledger_of(1000, 3, False)
    assert [row.split(" and ")[0] for row in checks.device_answers_due(mixed, "tpu")] == ["a window carried >= 512 lanes"]
    # a failed device launch is no answer; an empty ledger owes one of either kind
    failed = [{**r, "error": "Boom"} for r in ledger_of(1000, 3, True)]
    assert len(checks.device_answers_due(failed, "tpu")) == 2
    assert checks.device_answers_due([], "tpu") == ["the launch ledger holds no device answer of either kind, verify or tree"]
    # cached lanes count towards a window's size, as `verify_host_answers` counts them
    cached = [{"kind": "verify", "backend": "host", "height_lo": 1, "height_hi": 16, "rows": 12, "rows_cached": 500}]
    assert any("512 lanes" in row for row in checks.device_answers_due(cached, "tpu"))


def test_the_last_write_is_asked_at_the_height_the_app_answers_at(monkeypatch):
    """The store (and `/status`) is a block ahead of the app while that
    block is executed: the check reads the app's own height, before and
    after, and takes the write of any height from the first reading to the
    block in execution after the second."""
    from benchmark.lib import checks

    class Record:
        n_blocks = 9
        # fresh keys: a key is written by one height; fixed keys: one key, rewritten by every block
        last_write = [[f"{h:02x}", f"a{h}"] for h in range(1, 10)]

    def node(app_heights, value, asked):
        heights = iter(app_heights)

        def call(port, route):
            asked.append(route)
            if route == "abci_info":
                return {"last_block_height": next(heights)}
            assert route.startswith("abci_query?data=")
            return {"value": value}

        return call

    asked: list[str] = []
    monkeypatch.setattr(checks.rpc, "call", node([5, 6], "a5", asked))
    assert checks.check_last_write(0, Record) == []
    assert asked == ["abci_info", "abci_query?data=05", "abci_info"] and "status" not in " ".join(asked)
    # the app held the key of height 5 and answers with another height's write
    monkeypatch.setattr(checks.rpc, "call", node([5, 5], "a4", []))
    assert "not the write of heights 5..6" in checks.check_last_write(0, Record)[0]
    Record.last_write = [["6b", f"v{h}"] for h in range(1, 10)]
    for app, value, ok in (([5, 5], "v5", True), ([5, 5], "v6", True), ([5, 7], "v8", True), ([5, 5], "v4", False),
                           ([5, 5], "v7", False), ([9, 9], "v9", True)):
        monkeypatch.setattr(checks.rpc, "call", node(app, value, []))
        assert (checks.check_last_write(0, Record) == []) is ok, (app, value)
    monkeypatch.setattr(checks.rpc, "call", node([0], "", []))
    assert "applied no block" in checks.check_last_write(0, Record)[0]


def test_a_slow_answer_is_a_latency_and_not_a_failure():
    """A read that answered right, late, is counted by entry.reads_over_1s
    and stays in the tail; only a read that errored is left out of both."""
    import importlib.util

    def reader(name):
        spec = importlib.util.spec_from_file_location("m", os.path.join(BENCH, "layer_metrics", name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.reduce

    reads = [{"ok": True, "due": i / 20, "end": i / 20 + 0.005} for i in range(96)]
    reads += [{"ok": True, "due": 5.0, "end": 6.2}, {"ok": True, "due": 5.05, "end": 6.06},
              {"ok": True, "due": 5.1, "end": 6.1}, {"ok": False, "due": 5.2, "end": 15.2}]
    obs = {"reads": reads}
    assert reader("entry.reads_over_1s")(obs) == 2
    assert reader("entry.rpc_p95_ms")(obs) == pytest.approx(5.0)
    assert reader("entry.reads_over_1s")({"reads": reads[:96]}) == 0


def test_trace_reduce_on_the_recorded_trace():
    from benchmark.lib import trace_reduce

    with open(os.path.join(BENCH, "lib", "recorded_trace.json")) as f:
        rec = json.load(f)
    red = trace_reduce.reduce(rec["trace"], rec["window_s"])
    want = rec["expect"]
    assert red["chips"] == want["chips"]
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert trace_reduce.module_seconds(red, "verify_tables_kernel") == pytest.approx(
        want["verify_tables_kernel_s"], rel=1e-9
    )
    assert 0 < red["busy_s"] < red["window_s"]
    gaps = sum(d for _s, d in red["gaps"])
    assert gaps + red["busy_s"] == pytest.approx(red["window_s"], rel=1e-6)
    assert trace_reduce.top_ops(red, 3)[0][0] == want["top_op"]
    # an idle gap inside a launch's finalize is labelled by it
    launch = {"kind": "verify", "t": 1000.0 + want["gap_at_s"] + 0.5, "finalize_s": 1.0, "in_flight_s": 0.0, "host_prep_s": 0.0}
    labels = dict(trace_reduce.label_gaps(red, 1000.0, [launch]))
    assert "verify.finalize" in labels
    # a trace with no device plane gives nothing to read
    assert trace_reduce.reduce({"planes": [{"name": "/host:CPU", "lines": []}]}, 1.0) is None


def test_reference_merkle_and_sign_bytes_agree_with_the_generator(tmp_path):
    from benchmark.lib import chain, reference

    config = json.load(open(os.path.join(BENCH, "configs", "fastsync-100.json")))
    config["validators"] = 7
    mix = json.load(open(os.path.join(BENCH, "traffic", "sparse.json")))
    rec = chain.build_chain(config, mix, seed=5, n_blocks=20, home=str(tmp_path / "home"), workers=0)
    # ToValidators(20, 10): 20, 30, ... by key index, whatever the set's order
    assert sorted(rec.powers) == [20 + 10 * i for i in range(7)]
    entries = rec.tail_entries()
    assert [h for _b, h, _c in entries] == list(range(4, 20))
    from tendermint_tpu.types.block import Block

    blk = Block.decode(bytes.fromhex(rec.tail_blocks[3]))
    assert reference.merkle_root([bytes(t) for t in blk.data.txs]) == blk.header.data_hash
    vote = next(v for v in entries[0][2].precommits if v is not None)
    served = {
        "height": vote.height, "round": vote.round, "timestamp": vote.timestamp, "type": vote.type,
        "block_id": {"hash": vote.block_id.hash.hex(), "parts": {
            "total": vote.block_id.parts_header.total, "hash": vote.block_id.parts_header.hash.hex()}},
    }
    assert reference.sign_bytes(rec.chain_id, served) == vote.sign_bytes(rec.chain_id)


# -- a whole run, on a tiny deployment ---------------------------------------------


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A temp copy of the benchmark with one more deployment, traffic mix,
    per-layer metric and cell dropped in as new files and entries: no file
    that was there is edited."""
    top = tmp_path_factory.mktemp("bench_copy")
    shutil.copytree(BENCH, top / "benchmark", ignore=shutil.ignore_patterns("out", "cache", "__pycache__"))
    os.symlink(os.path.join(ROOT, "tendermint_tpu"), top / "tendermint_tpu")
    b = bench_json()
    for n_vals in (16, 40):
        cfg = json.load(open(os.path.join(BENCH, "configs", "fastsync-100.json")))
        cfg.update(name=f"tiny{n_vals}", validators=n_vals, absent_votes=1)
        json.dump(cfg, open(top / "benchmark" / "configs" / f"tiny{n_vals}.json", "w"))
        b["configs"].append({"name": f"tiny{n_vals}", "source": "test", "file": f"benchmark/configs/tiny{n_vals}.json", "reduced": [], "why": "test"})
        b["workloads"].append({"name": f"tiny{n_vals}.trickle", "config": f"tiny{n_vals}", "traffic": "trickle", "chips": 1, "why": "test"})
        json.dump(
            {"chain_blocks": 1200, "metrics": ["catchup_blocks_per_s", "setup_s"],
             "layer_metrics": ["entry.rpc_median_ms", "entry.reads_issued", "verify.host_fallbacks", "device.idle_share"]},
            open(top / "benchmark" / "cells" / f"tiny{n_vals}.trickle.json", "w"),
        )
    json.dump(
        {"name": "trickle", "driver": "catchup", "txs": {"kind": "fresh_keys", "per_block": 2},
         "reads": {"kinds": ["status"], "per_s": 10}, "warm_blocks": 8},
        open(top / "benchmark" / "traffic" / "trickle.json", "w"),
    )
    json.dump(
        {"name": "entry.reads_issued", "layer": "entry", "unit": "count", "better": "higher",
         "source": "host_clock", "moves": "catchup_blocks_per_s"},
        open(top / "benchmark" / "layer_metrics" / "entry.reads_issued.json", "w"),
    )
    (top / "benchmark" / "layer_metrics" / "entry.reads_issued.py").write_text(
        "def reduce(obs):\n    return len(obs['reads'])\n"
    )
    json.dump(b, open(top / "BENCHMARK.json", "w"))
    return top


def run_cell(top, *args, allow_cpu=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    argv = [sys.executable, "benchmark/run.py", *args]
    if allow_cpu:
        argv.append("--allow-cpu-for-tests")
    return subprocess.run(argv, cwd=top, env=env, capture_output=True, text=True, timeout=300)


def last_line(proc) -> dict:
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_a_tiny_cell_end_to_end_from_new_files_alone(copy):
    proc = run_cell(copy, "--workload", "tiny16.trickle", "--seed", "2147483659", "--seconds", "2", "--trace", "0")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = last_line(proc)
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 20
    assert set(line["metrics"]) == {"catchup_blocks_per_s", "setup_s"}
    assert line["metrics"]["catchup_blocks_per_s"]["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    # each number compared beside its limit: last in the line, and the last lines of standard error
    assert list(line)[-1] == "compared" and len(line["compared"]) >= 7
    assert all(number == limit == 0 for number, limit in line["compared"].values())
    # (the last three: the node's treatment of its peers, all sound here)
    assert list(line["compared"])[-4:] == ["no_rate_read", "forged_blocks_applied", "peers_debited_undue", "liars_kept"]
    assert proc.stderr.strip().splitlines()[-1] == "compared liars_kept: 0 (limit 0)"
    # one serving peer, and the node ended with it
    assert line["peers"] == {"served": 1, "connected_at_close": 1, "pool_peers_at_end": 1}
    assert line["device"]["platform"] == "cpu"
    # every line that carries a number names the device
    for row in proc.stdout.splitlines()[:-1]:
        if re.search(r"\d", row.split("]", 1)[-1]):
            assert "platform=" in row and "device_kind=" in row and "devices=" in row, row
    detail = json.load(open(copy / "benchmark" / "out" / "tiny16.trickle-2147483659.json"))
    assert detail["result"] == line and detail["checks"]["failures"] == []


def test_a_traced_run_reports_the_layer_metrics_it_can_read(copy):
    proc = run_cell(copy, "--workload", "tiny16.trickle", "--seed", "7", "--seconds", "2", "--trace", "1")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = last_line(proc)
    assert line["correct"] is True
    # the dropped-in metric is found; the trace metric finds no device plane
    # on the CPU and is left out of the line
    assert line["metrics"]["entry.reads_issued"] == {"value": 20.0, "unit": "count"}
    assert line["metrics"]["verify.host_fallbacks"]["value"] == 0.0
    assert "device.idle_share" not in line["metrics"]


@pytest.mark.parametrize("control", ["accept_all", "apphash_off_by_one"])
def test_correct_turns_false_under_a_broken_guarantee(copy, control):
    proc = run_cell(
        copy, "--workload", "tiny16.trickle", "--seed", "11", "--seconds", "2", "--trace", "0", "--control", control
    )
    assert proc.returncode == 1, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert last_line(proc)["correct"] is False
    assert "NOT CORRECT" in proc.stdout


def test_correct_turns_false_when_the_host_answers_a_device_sized_launch(copy):
    """40 validators x 16 commits = 640 lanes >= 512: a launch that size is
    the device's to answer, and on the CPU the host library answers it."""
    proc = run_cell(copy, "--workload", "tiny40.trickle", "--seed", "13", "--seconds", "3", "--trace", "1")
    assert proc.returncode == 1, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = last_line(proc)
    assert line["correct"] is False and line["metrics"]["verify.host_fallbacks"]["value"] > 0
    assert "were not answered by the device" in proc.stdout


def test_without_an_accelerator_nothing_is_printed(copy):
    proc = run_cell(
        copy, "--workload", "tiny16.trickle", "--seed", "1", "--seconds", "1", "--trace", "0", allow_cpu=False
    )
    assert proc.returncode == 3 and "{" not in proc.stdout, proc.stdout[-2000:]
    assert not [p for p in os.listdir("/proc") if p.isdigit() and _is_child_of_run(p, str(copy))]


def _is_child_of_run(pid: str, marker: str) -> bool:
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            return marker.encode() in f.read()
    except OSError:
        return False


def test_alone_the_benchmark_fails_without_a_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.ignore_patterns("out", "cache", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    proc = run_cell(tmp_path, "--workload", "fastsync-100.sparse", "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode == 4 and proc.stdout == ""
