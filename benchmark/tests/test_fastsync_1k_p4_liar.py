"""The cell `fastsync-1k-p4.liar` as the benchmark lists it (PR 47), on the
CPU: the deployment's, the mix's and the cell's files are the withheld ones
(the cell's list begun as withheld, three readers behind it); the cell's
entries; the three readers' arithmetic over hand-made pulls; and what the
program now does where the cell met its fault one time in seventeen: a
forged block that is the LAST of a verify window debits its server alone.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

The cells are `listing.py`'s, found by what their files say; none is named
in a list here. Nothing here yields a device number.
"""

from __future__ import annotations

import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark.tests import listing  # noqa: E402

CELL = "fastsync-1k-p4.liar"
NEW = ["fastsync.redos_in_window", "fastsync.redo_sound_blocks_dropped", "fastsync.redo_recover_ms"]
TREE = listing.Listing()


def reader(name):
    spec = importlib.util.spec_from_file_location("m", os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce


# -- the files and the entries -------------------------------------------------------------


def test_the_cell_is_the_withheld_files_and_three_readers_behind_them():
    config, mix = CELL.rsplit(".", 1)
    assert TREE.raw("configs", config + ".json") == TREE.raw("tests", "withheld", config + ".json")
    assert TREE.raw("traffic", mix + ".json") == TREE.raw("tests", "withheld", mix + ".json")
    kept, listed = TREE.load("tests", "withheld", CELL + ".json"), TREE.load("cells", CELL + ".json")
    assert listed == {**kept, "layer_metrics": kept["layer_metrics"] + NEW}
    assert listed["chain_blocks"] == 800 and listed["trace_seconds"] == 6
    # byte for byte but for the three names: the withheld file's lines, in order, are the listed file's
    was = TREE.raw("tests", "withheld", CELL + ".json").decode().splitlines()
    now = TREE.raw("cells", CELL + ".json").decode().splitlines()
    added = [row for row in now if row.strip().strip('",') in NEW]
    assert len(added) == 3 and [row.rstrip(",") for row in now if row not in added] == [row.rstrip(",") for row in was]


def test_the_cells_entries_say_what_its_files_say():
    entry = TREE.workload(CELL)
    assert entry == {"name": CELL, "config": "fastsync-1k-p4", "traffic": "liar", "chips": 1, "why": entry["why"]}
    assert len(entry["why"]) <= 200 and "\n" not in entry["why"]
    assert TREE.cells[-1] == CELL and TREE.contract["configs"][-1]["name"] == "fastsync-1k-p4"
    doc, rule = TREE.deployment(CELL), TREE.mix(CELL)["peers"]
    assert doc["peers"] == 4 and doc["validators"] == 1000 and doc["reduced"] == ["source_blocks"]
    assert "debited and dropped" in doc["guarantees"][3]
    assert rule == {"kind": "flip_sig", "liars": [3], "from_height": 200, "every": 7}
    # the cells under a peer rule are the ones that list the redo's readers, and they list them last
    ruled = [cell for cell in TREE.cells if "peers" in TREE.mix(cell)]
    assert CELL in ruled
    order = list(TREE.per_layer)
    assert order[-3:] == NEW
    for name in NEW:
        assert TREE.per_layer[name]["workloads"] == ruled
        meta = TREE.load("layer_metrics", name + ".json")
        assert (meta["layer"], meta["source"], meta["moves"]) == ("fast-sync", "program_counter", "catchup_blocks_per_s")
        assert meta["better"] == "lower" and meta["what"]
    assert [TREE.per_layer[name]["unit"] for name in NEW] == ["count", "blocks", "ms"]
    for cell in ruled:
        assert TREE.lists[cell][-3:] == NEW
    # no new kernel came: the verify kernel's two readers, as the one-peer cell of the same deployment size
    assert set(listing.VERIFY_KERNEL) <= set(TREE.lists[CELL])


# -- the three readers ----------------------------------------------------------------------


def pulls(redos=(), others=None, recover=None):
    """A `/metrics` pull of a program that has the redo series: the four
    causes, both `whose`, the histogram's sum and count."""
    from benchmark.lib import rpc

    text = ["tendermint_fastsync_blocks_applied_total 500.0"]
    for cause, value in redos:
        text.append(f'tendermint_fastsync_redos_total{{cause="{cause}"}} {value!r}')
    if others is not None:
        text.append(f'tendermint_fastsync_redo_blocks_dropped_total{{whose="blamed"}} {others[0]!r}')
        text.append(f'tendermint_fastsync_redo_blocks_dropped_total{{whose="others"}} {others[1]!r}')
    if recover is not None:
        text.append(f"tendermint_fastsync_redo_recover_seconds_sum {recover[0]!r}")
        text.append(f"tendermint_fastsync_redo_recover_seconds_count {recover[1]!r}")
        text.append(f'tendermint_fastsync_redo_recover_seconds_bucket{{le="+Inf"}} {recover[1]!r}')
    return rpc.parse_metrics("\n".join(text) + "\n")


ZERO = [("block_id", 0.0), ("verdict", 0.0), ("prep", 0.0), ("body", 0.0)]


def test_a_program_without_the_series_gives_none():
    """The parent's side of the cell's first line: null for the three."""
    obs = {"metrics_start": pulls(), "metrics_end": pulls()}
    assert [reader(name)(obs) for name in NEW] == [None, None, None]


def test_two_redos_no_block_of_anothers_and_a_recovery_of_four_tenths_of_a_second():
    start = pulls(redos=ZERO, others=(0.0, 0.0), recover=(0.0, 0.0))
    end = pulls(
        redos=[("block_id", 1.0), ("verdict", 1.0), ("prep", 0.0), ("body", 0.0)],
        others=(61.0, 0.0), recover=(0.4, 1.0),
    )
    obs = {"metrics_start": start, "metrics_end": end}
    assert reader(NEW[0])(obs) == 2.0
    assert reader(NEW[1])(obs) == 0.0
    assert reader(NEW[2])(obs) == pytest.approx(400.0)
    # what was there before the window is not the window's
    obs = {"metrics_start": end, "metrics_end": end}
    assert reader(NEW[0])(obs) == 0.0 and reader(NEW[1])(obs) == 0.0
    # no recovery ended in the window: nothing to average
    assert reader(NEW[2])(obs) is None
    # a redo that forgot the suffix, as until PR 47: the sound blocks show
    end = pulls(redos=[("verdict", 1.0)], others=(50.0, 150.0), recover=(0.0, 0.0))
    assert reader(NEW[1])({"metrics_start": start, "metrics_end": end}) == 150.0


def test_the_program_declares_the_series_the_readers_read():
    """The names are the program's: a scrape of its registry, before any
    redo, parses to a run the three readers read 0, 0 and None from."""
    from benchmark.lib import redos, rpc
    from tendermint_tpu.telemetry import REGISTRY, metrics  # noqa: F401 - the import declares the families

    scrape = rpc.parse_metrics(REGISTRY.prometheus_text())
    for name in (redos.REDOS, redos.DROPPED, redos.RECOVER + "_count", redos.RECOVER + "_sum"):
        assert name in scrape, name
    assert {labels["cause"] for labels, _ in scrape[redos.REDOS]} == {"block_id", "verdict", "prep", "body"}
    assert {labels["whose"] for labels, _ in scrape[redos.DROPPED]} == {"blamed", "others"}
    obs = {"metrics_start": scrape, "metrics_end": scrape}
    assert [reader(name)(obs) for name in NEW] == [0.0, 0.0, None]


# -- the fault the cell met one time in seventeen ------------------------------------------------


@pytest.mark.parametrize("position", [17, 33])
def test_a_forged_block_that_is_a_windows_last_debits_its_server_alone(position, tmp_path):
    """`test_peers.py`'s witness, strictly (its own test of it is an
    `xfail` that is not strict, and now passes unexpectedly: a `benchmark`
    PR takes the marker off). The plain reference: the block at `position`
    came from peer (position - 1) % 3 with bytes that are not the
    record's, every other block is the record's, so that peer is the one
    liar; the program debits the liars, once each, and nobody else."""
    from benchmark.tests import test_peers

    liars = [f"peer{(position - 1) % 3}"]
    assert test_peers.blamed_for_a_forged_block_at(position, tmp_path) == liars
