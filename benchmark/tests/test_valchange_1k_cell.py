"""The cell `valchange-1k.rotate` as the benchmark lists it (PR 40), on the
CPU: the deployment's and the mix's files are the withheld ones copied, the
cell's list is `fastsync-1k.sparse`'s less PR 31's five, then PR 36's three
readers, then the two this PR adds; every listed reader names the cell; the
two new readers' arithmetic over hand-made pulls; and one rehearsal of the
listed cell on a 16-validator scratch deployment.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

Nothing here yields a device number.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

CELL = "valchange-1k.rotate"
PR31 = [
    "verify.pad_lane_share", "verify.single_commit_launch_share", "process.gc_pause_share",
    "fastsync.valset_roots_per_block", "fastsync.vote_encodes_per_block",
]
PR36 = ["fastsync.boundary_window_share", "verify.table_cache_miss_share", "verify.table_incremental_share"]
NEW = ["verify.table_keys_built_per_block", "verify.table_build_joined_share"]
# listed since PR 42: the table build's reader here alone (a static set builds in set-up only), and in
# every cell, last, PR 38's seventeen and the reader of a `/block` answer's bytes
BUILD = "verify.table_build_ms"
EVENTS = "tendermint_verify_table_cache_total"
KEYS = "tendermint_verify_table_keys_built_total"


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


LAST = [*load("tests", "withheld", "own_work.json")["layer_metrics"], "entry.block_answer_bytes"]


def reader(name):
    spec = importlib.util.spec_from_file_location("m", os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce


# -- the files ----------------------------------------------------------------------


@pytest.mark.parametrize("listed, kept", [("configs/valchange-1k.json", "valchange-1k.json"), ("traffic/rotate.json", "rotate.json")])
def test_the_listed_file_is_the_withheld_file_byte_for_byte(listed, kept):
    with open(os.path.join(BENCH, listed), "rb") as a, open(os.path.join(HERE, "withheld", kept), "rb") as b:
        assert a.read() == b.read()


def test_the_cell_lists_what_the_issue_names():
    cell, sparse = load("cells", CELL + ".json"), load("cells", "fastsync-1k.sparse.json")
    assert cell["chain_blocks"] == 800 and cell["trace_seconds"] == 6
    assert cell["metrics"] == ["catchup_blocks_per_s", "setup_s"]
    # `fastsync-1k.sparse`'s list with PR 31's five in it (since PR 42; a test pinned them to the two
    # `fastsync-1k` cells before), then this cell's six, then what every cell lists last
    assert cell["layer_metrics"] == [n for n in sparse["layer_metrics"] if n not in LAST] + PR36 + NEW + [BUILD] + LAST
    assert set(PR31) <= set(sparse["layer_metrics"]) and len(cell["layer_metrics"]) == len(sparse["layer_metrics"]) + 6
    # what the withheld cell file asked for, and what came after it
    withheld = load("tests", "withheld", CELL + ".json")
    assert withheld["layer_metrics"] + NEW + [BUILD] + LAST == cell["layer_metrics"]
    assert {k: v for k, v in withheld.items() if k != "layer_metrics"} == {k: v for k, v in cell.items() if k != "layer_metrics"}


def test_the_cells_entries_say_what_its_files_say_and_its_six_readers_are_a_changing_sets():
    """The cell's own entries, found by name. What every listed cell's
    entries keep, whichever cells there are, is `listing.py`'s, run by
    test_listing.py: an entry's `workloads` is the cells whose file lists
    the reader, and the six of a changing set are listed exactly where
    the mix carries `valset`."""
    from benchmark.tests import listing

    tree = listing.Listing()
    entry = tree.workload(CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == ("valchange-1k", "rotate", 1)
    assert len(entry["why"]) <= 200 and "\n" not in entry["why"]
    config, doc = tree.config_entry("valchange-1k"), load("configs", "valchange-1k.json")
    assert config["file"] == "benchmark/configs/valchange-1k.json" and config["reduced"] == ["source_blocks"] == doc["reduced"]
    assert config["source"] == doc["source"] and len(config["source"]) <= 200 and len(config["why"]) <= 200
    assert doc["app"] == "persistent_kvstore" and doc["validators"] == 1000 and load("traffic", "rotate.json")["name"] == "rotate"
    assert "valset" in tree.mix(CELL) and PR36 + NEW + [BUILD] == listing.VALSET_READERS
    per_layer = tree.per_layer
    for name in PR36 + NEW + [BUILD]:
        assert per_layer[name]["workloads"] == [c for c in tree.cells if "valset" in tree.mix(c)]
    for name in NEW:
        meta = load("layer_metrics", name + ".json")
        assert (meta["layer"], meta["source"], meta["what"] != "") == ("verify spine", "program_counter", True)
        assert {k: per_layer[name][k] for k in ("unit", "better")} == {k: meta[k] for k in ("unit", "better")}
    assert (per_layer[NEW[0]]["unit"], per_layer[NEW[0]]["better"]) == ("keys/block", "lower")
    assert (per_layer[NEW[1]]["unit"], per_layer[NEW[1]]["better"]) == ("%", "higher")


# -- the two new readers ---------------------------------------------------------------


def pull(blocks, **series):
    from benchmark.lib import rpc

    rows = [f"tendermint_fastsync_blocks_applied_total {blocks!r}\n"]
    for name, value in series.items():
        base, label, text = name.split("__")
        rows.append(f'{base}{{{label}="{text}"}} {value!r}\n')
    return rpc.parse_metrics("".join(rows))


def test_the_two_new_readers_over_a_hand_made_pair_of_pulls():
    start = pull(48, **{EVENTS + "__event__hit": 30, EVENTS + "__event__miss": 6, EVENTS + "__event__joined": 4,
                        KEYS + "__how__host": 7, KEYS + "__how__device": 1024})
    # 300 blocks, fifteen cycles: 30 builds, 20 of them met in flight by the next launch, 45 keys
    end = pull(348, **{EVENTS + "__event__hit": 190, EVENTS + "__event__miss": 36, EVENTS + "__event__joined": 24,
                       KEYS + "__how__host": 52, KEYS + "__how__device": 1024})
    obs = {"metrics_start": start, "metrics_end": end, "window": [1000.0, 1030.0]}
    assert reader(NEW[0])(obs) == pytest.approx(45 / 300) == pytest.approx(0.15)
    assert reader(NEW[1])(obs) == pytest.approx(100 * 20 / 50)
    # a key built twice, by prebuild and by the launch: 0.30, and nothing ever joins
    twice = pull(348, **{EVENTS + "__event__hit": 190, EVENTS + "__event__miss": 66, EVENTS + "__event__joined": 4,
                         KEYS + "__how__host": 97, KEYS + "__how__device": 1024})
    obs = {"metrics_start": start, "metrics_end": twice, "window": [1000.0, 1030.0]}
    assert reader(NEW[0])(obs) == pytest.approx(0.30) and reader(NEW[1])(obs) == 0.0
    # a full build inside the window shows as a thousand keys
    full = pull(348, **{EVENTS + "__event__miss": 7, EVENTS + "__event__joined": 4, KEYS + "__how__host": 7, KEYS + "__how__device": 2048})
    assert reader(NEW[0])({"metrics_start": start, "metrics_end": full}) == pytest.approx(1024 / 300)


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_gives_none_where_there_is_nothing_to_read(name):
    # the parent's program: the cache's events but no `joined`, no keys series
    older = {"metrics_start": pull(48, **{EVENTS + "__event__hit": 40, EVENTS + "__event__miss": 6}),
             "metrics_end": pull(408, **{EVENTS + "__event__hit": 400, EVENTS + "__event__miss": 36})}
    assert reader(name)(older) is None
    # the series there, and no block applied, no build
    same = pull(48, **{EVENTS + "__event__miss": 6, EVENTS + "__event__joined": 0, KEYS + "__how__host": 3})
    assert reader(name)({"metrics_start": same, "metrics_end": same}) is None


# -- the listed cell, rehearsed on a scratch deployment ------------------------------------


@pytest.fixture()
def scratch(tmp_path):
    """A temp copy of the benchmark with a 16-validator cut of the listed
    deployment dropped in beside it, under the listed mix and the listed
    cell's own list of readers."""
    top = tmp_path / "copy"
    shutil.copytree(BENCH, top / "benchmark", ignore=shutil.ignore_patterns("out", "cache", "__pycache__"))
    os.symlink(os.path.join(ROOT, "tendermint_tpu"), top / "tendermint_tpu")
    b = bench_json()
    config = load("configs", "valchange-1k.json")
    config.update(name="scratch16", validators=16)
    with open(top / "benchmark" / "configs" / "scratch16.json", "w") as f:
        json.dump(config, f)
    cell = load("cells", CELL + ".json")
    cell["chain_blocks"] = 1500  # blocks this light go by at a hundred a second
    with open(top / "benchmark" / "cells" / "scratch16.rotate.json", "w") as f:
        json.dump(cell, f)
    b["configs"].append({"name": "scratch16", "source": "test", "file": "benchmark/configs/scratch16.json", "reduced": [], "why": "test"})
    b["workloads"].append({"name": "scratch16.rotate", "config": "scratch16", "traffic": "rotate", "chips": 1, "why": "test"})
    with open(top / "BENCHMARK.json", "w") as f:
        json.dump(b, f)
    return top


def test_the_listed_cell_rehearsed_on_the_cpu_reports_every_reader_it_can(scratch, monkeypatch, capfd):
    """The whole run in this process through `run.py --allow-cpu-for-tests`,
    with the verifier a TPU process has in place of the CPU's host verifier
    (as `test_valchange_1k.py` rehearses it): every window here is under 512
    lanes and goes to the host library, so no launch ever waits for a build
    (`joined` 0) and `prebuild` builds every new set's table."""
    import jax  # noqa: F401 - the driver needs a backend, here the CPU's

    from tendermint_tpu.ops import ed25519_tables
    from tendermint_tpu.services import verifier as verifier_mod
    from tendermint_tpu.services.batcher import CoalescingVerifier
    from tendermint_tpu.services.resilient import ResilientVerifier

    host_build = ed25519_tables.host_build_key_tables
    monkeypatch.setattr(ed25519_tables, "build_key_tables", lambda keys: host_build([bytes(k) for k in keys]))
    monkeypatch.setattr(
        verifier_mod, "_DEFAULT", CoalescingVerifier(ResilientVerifier(verifier_mod.TableBatchVerifier()))
    )
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(scratch)
    spec = importlib.util.spec_from_file_location("bench_run_copy_40", scratch / "benchmark" / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    monkeypatch.setattr(sys, "path", list(sys.path))
    code = run.main(["--workload", "scratch16.rotate", "--seed", "4000000013", "--seconds", "3",
                     "--trace", "1", "--allow-cpu-for-tests"])
    out, err = capfd.readouterr()
    assert code in (0, 1), out[-3000:] + err[-3000:]
    # (every row: the last-write check asks at the app's own height since PR 42 and races the apply no more)
    wrong = [row for row in out.splitlines() if "NOT CORRECT" in row]
    assert not wrong, wrong
    line = json.loads(out.strip().splitlines()[-1])
    got = line["metrics"]
    # every reader the cell lists but the three that need a device's trace
    listed = load("cells", CELL + ".json")["layer_metrics"]
    missing = [n for n in listed if n not in got]
    assert set(missing) <= {"kernel.verify_us_per_sig", "kernel.verify_tables_roofline", "device.idle_share",
                            "fastsync.commits_per_launch", "verify.prep_ms_per_commit",
                            "verify.finalize_ms_per_launch",
                            # no launch here is the device's: the two launch-shape readers find nothing
                            "verify.pad_lane_share", "verify.single_commit_launch_share"}, missing
    assert set(PR31[2:]) <= set(got) and set(LAST) <= set(got) and BUILD in got
    assert got["fastsync.boundary_window_share"]["value"] > 50
    assert got["verify.table_incremental_share"]["value"] > 0
    assert got["verify.table_build_joined_share"] == {"value": 0.0, "unit": "%"}
    # three keys a 20-height cycle built once are 0.15; a set asked for while its
    # neighbour's build is still in flight concatenates to an older set and builds more
    assert 0.1 <= got["verify.table_keys_built_per_block"]["value"] <= 1.0
    assert got["verify.table_keys_built_per_block"]["unit"] == "keys/block"
    assert got["verify.host_fallbacks"]["value"] == 0.0
    for name in ("record_valsets_differ", "sample_differ", "sample_valset_hash_differ", "sample_commit_differ"):
        assert line["compared"][name] == [0, 0]
    assert list(line)[-2:] == ["table_cache_events", "compared"]
