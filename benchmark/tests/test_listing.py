"""The rules of a listing (`listing.py`), over the tree as it stands and
over a copy with a fifth cell listed by additions alone (PR 43), on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

A case a cell a rule: the count grows with the benchmark. The copy is the
listing the next `model_config` PR must make (`listing.list_by_additions`:
the withheld `fastsync-1k-p4.liar` until the tree lists it), and two
listings altered by hand show that the rules bite. Nothing here runs the
benchmark or yields a device number.
"""

from __future__ import annotations

import copy
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.tests import listing  # noqa: E402

TREE = listing.Listing()
CASES = [("tree", cell) for cell in TREE.cells] + [("copy", cell) for cell in listing.cells_after_additions(TREE)]


@pytest.fixture(scope="module")
def listings(tmp_path_factory):
    return {"tree": TREE, "copy": listing.list_by_additions(tmp_path_factory.mktemp("listed"), TREE)}


@pytest.mark.parametrize("where, cell", CASES, ids=[f"{where}:{cell}" for where, cell in CASES])
@pytest.mark.parametrize("rule", listing.CELL_RULES, ids=[rule.__name__ for rule in listing.CELL_RULES])
def test_a_listed_cell_keeps_the_rule(listings, rule, where, cell):
    rule(listings[where], cell)


@pytest.mark.parametrize("where", ["tree", "copy"])
@pytest.mark.parametrize("name", listing.withheld_names())
def test_a_withheld_file_is_not_listed_or_listed_as_it_is(listings, name, where):
    listing.withheld_file_is_listed_as_it_is(listings[where], name)


def test_the_copy_is_the_tree_and_additions_alone(listings):
    """What `list_by_additions` made: every entry of the tree's is there
    in its place, unchanged but for a cell's name appended to a reader's
    `workloads`; every file of the tree's is there byte for byte (but the
    cell's that took the made-up reader, where no withheld cell was left
    to list); the made-up reader's entry is `per_layer`'s last and its
    name the cell's last."""
    tree, made = listings["tree"], listings["copy"]
    added = made.cells[-1] if listing.cell_to_add(tree) else None
    cell = added or tree.cells[-1]
    assert made.cells == listing.cells_after_additions(tree)
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert made.contract[key] == tree.contract[key]
    for key in ("configs", "workloads"):
        assert made.contract[key][: len(tree.contract[key])] == tree.contract[key]
    for was, now in zip(tree.contract["per_layer"], made.contract["per_layer"]):
        assert {**now, "workloads": was["workloads"]} == was
        assert now["workloads"] in (was["workloads"], was["workloads"] + [cell])
    assert [m["name"] for m in made.contract["per_layer"][len(tree.contract["per_layer"]) :]] == [listing.MADE_UP]
    assert made.per_layer[listing.MADE_UP]["workloads"] == [cell] and made.lists[cell][-1] == listing.MADE_UP
    for folder in ("", "cells", "configs", "traffic", "layer_metrics", "tx_rules", "valset_rules", "peer_rules", "drivers", "lib"):
        for name in sorted(os.listdir(tree.path(folder))):
            if os.path.isfile(tree.path(folder, name)) and not (added is None and (folder, name) == ("cells", cell + ".json")):
                assert made.raw(folder, name) == tree.raw(folder, name), os.path.join(folder, name)
    if added:
        config, mix = added.rsplit(".", 1)
        assert made.workload(added) == {"name": added, "config": config, "traffic": mix, "chips": 1, "why": made.workload(added)["why"]}
        assert made.lists[added][:-1] == tree.load("tests", "withheld", added + ".json")["layer_metrics"]


# -- the rules bite ----------------------------------------------------------------------


@pytest.mark.parametrize("cell", listing.cells_after_additions(TREE))
def test_a_cells_name_struck_from_one_readers_workloads_fails(listings, cell):
    struck = copy.deepcopy(listings["copy"])
    reader = struck.lists[cell][len(struck.lists[cell]) // 2]
    struck.per_layer[reader]["workloads"].remove(cell)
    with pytest.raises(AssertionError, match=f"{reader}: its workloads lack {cell}, whose file lists it"):
        listing.cell_and_readers_name_each_other(struck, cell)
    # ... and no other cell's case does: the fault is named where it is
    for other in struck.cells:
        if other != cell:
            listing.cell_and_readers_name_each_other(struck, other)


def test_a_reader_listed_that_has_no_entry_fails(listings):
    bare = copy.deepcopy(listings["copy"])
    cell = bare.per_layer[listing.MADE_UP]["workloads"][0]
    bare.contract["per_layer"] = [m for m in bare.contract["per_layer"] if m["name"] != listing.MADE_UP]
    with pytest.raises(AssertionError, match=f"{cell} lists {listing.MADE_UP}, which has no entry in per_layer"):
        listing.cell_and_readers_name_each_other(bare, cell)


def test_a_reader_named_by_an_entry_and_not_listed_by_the_cell_fails(listings):
    extra = copy.deepcopy(listings["copy"])
    cell = extra.cells[0]
    reader = next(name for name, m in extra.per_layer.items() if cell not in m["workloads"])
    extra.per_layer[reader]["workloads"].insert(0, cell)
    with pytest.raises(AssertionError, match=f"{reader}: its workloads name {cell}, whose file does not list it"):
        listing.cell_and_readers_name_each_other(extra, cell)
    # appended behind a later cell, the name is also out of the cells' order
    extra.per_layer[reader]["workloads"] = extra.per_layer[reader]["workloads"][1:] + [cell]
    extra.lists[cell] = [*extra.lists[cell], reader]
    with pytest.raises(AssertionError, match="not in the cells' order"):
        listing.cell_and_readers_name_each_other(extra, cell)


def test_the_property_rules_bite_too(listings):
    made = listings["copy"]
    static = next(cell for cell in made.cells if "valset" not in made.mix(cell))
    # a static set's cell that lists the table build's reader
    wrong = copy.deepcopy(made)
    wrong.lists[static] = [listing.VALSET_READERS[-1], *wrong.lists[static]]
    with pytest.raises(AssertionError, match="listed under a static set"):
        listing.cell_lists_what_its_deployment_and_mix_make_due(wrong, static)
    # a reader of the benchmark's first days listed behind the seventeen
    wrong = copy.deepcopy(made)
    first = wrong.lists[static][0]
    wrong.lists[static] = [*wrong.lists[static][1:], first]
    with pytest.raises(AssertionError, match="listed behind the seventeen"):
        listing.cell_lists_own_work_last(wrong, static)
    # the seventeen out of their order
    wrong = copy.deepcopy(made)
    names = wrong.lists[static]
    a, b = names.index(listing.own_work()[0]), names.index(listing.own_work()[1])
    names[a], names[b] = names[b], names[a]
    with pytest.raises(AssertionError, match="in own_work.json's order"):
        listing.cell_lists_own_work_last(wrong, static)
    # an entry whose source is not its file's
    wrong = copy.deepcopy(made)
    wrong.config_entry(wrong.workload(static)["config"])["source"] += " (edited)"
    with pytest.raises(AssertionError, match="the entry's source is not its file's"):
        listing.cell_is_its_files(wrong, static)
