"""What a benchmark lists, and the rules every listing keeps (PR 43).

`Listing` reads a tree's `BENCHMARK.json` and the files it names: the cells
in `workloads` order, each cell's deployment and mix, `lists[cell]` (the
readers `cells/<cell>.json` lists) and `per_layer` by name. The rules
below are written over whatever it holds, once, where five rehearsals
each compared with a literal list of the four cells PR 42 left: a later
PR lists a cell by additions alone (new files, new entries, the cell's
name appended to each listed reader's `workloads`) and may edit no file
here, so a rule that names the cells would stop it. `list_by_additions`
makes such a listing of a withheld cell in a temp copy, and
`test_listing.py` runs every rule on the tree as it stands and on that
copy.

A rule fails by `AssertionError`; nothing here runs the benchmark.
"""

from __future__ import annotations

import copy
import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

READER_KEYS = ("name", "unit", "better", "source", "layer", "moves")

ANSWER = "entry.block_answer_bytes"
# PR 31's five: what 1,000 votes a block make large
PR31 = [
    "verify.pad_lane_share", "verify.single_commit_launch_share",
    "process.gc_pause_share", "fastsync.valset_roots_per_block", "fastsync.vote_encodes_per_block",
]
# a changing set's: PR 36's three, PR 40's two, and the table build's (a static set builds in set-up only)
VALSET_READERS = [
    "fastsync.boundary_window_share", "verify.table_cache_miss_share", "verify.table_incremental_share",
    "verify.table_keys_built_per_block", "verify.table_build_joined_share", "verify.table_build_ms",
]
# a device-sized tx tree's (PR 35). A cell that lists them does not list the verify kernel's two: at under a
# block a second the traced stretch holds the record of one launch and the device time of another, or none
HASH_READERS = [
    "hash.tree_ms_per_block", "hash.host_fallbacks", "kernel.merkle_us_per_leaf", "kernel.merkle_tree_roofline",
]
VERIFY_KERNEL = ["kernel.verify_us_per_sig", "kernel.verify_tables_roofline"]

# the withheld cells a later PR is to list, first in line first (PERF.md section 7)
WITHHELD_CELLS = ("fastsync-1k-p4.liar", "fastsync-1k-p4.sparse")
MADE_UP = "rehearsal.made_up_reader"


def own_work() -> list[str]:
    """PR 38's seventeen, in the order every cell lists them."""
    with open(os.path.join(HERE, "withheld", "own_work.json")) as f:
        return json.load(f)["layer_metrics"]


class Listing:
    """`BENCHMARK.json` at `root` and the files under `root/benchmark` it
    names. `contract` and `lists` are plain data: a negative case alters a
    deep copy of them and leaves the files alone."""

    def __init__(self, root: str = ROOT):
        self.root = str(root)
        self.bench = os.path.join(self.root, "benchmark")
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.contract = json.load(f)
        self.cells = [w["name"] for w in self.contract["workloads"]]
        self.lists = {cell: self.load("cells", cell + ".json")["layer_metrics"] for cell in self.cells}

    def path(self, *parts) -> str:
        return os.path.join(self.bench, *parts)

    def load(self, *parts):
        with open(self.path(*parts)) as f:
            return json.load(f)

    def raw(self, *parts) -> bytes:
        with open(self.path(*parts), "rb") as f:
            return f.read()

    @property
    def per_layer(self) -> dict:
        return {m["name"]: m for m in self.contract["per_layer"]}

    def workload(self, cell: str) -> dict:
        return next(w for w in self.contract["workloads"] if w["name"] == cell)

    def config_entry(self, name: str) -> dict:
        return next(c for c in self.contract["configs"] if c["name"] == name)

    def deployment(self, cell: str) -> dict:
        return self.load("configs", self.workload(cell)["config"] + ".json")

    def mix(self, cell: str) -> dict:
        return self.load("traffic", self.workload(cell)["traffic"] + ".json")


# -- the rules --------------------------------------------------------------------------


def cell_and_readers_name_each_other(listing: Listing, cell: str) -> None:
    """Every name the cell's file lists has an entry and a file pair that
    agree; an entry names the cell exactly when the cell's file lists the
    reader, and names cells in `workloads` order. Held for every cell, that
    is: an entry's `workloads` is the listed cells whose file lists it."""
    per_layer, names = listing.per_layer, listing.lists[cell]
    assert names and len(set(names)) == len(names), f"{cell}: a reader listed twice, or none"
    for name in names:
        assert name in per_layer, f"{cell} lists {name}, which has no entry in per_layer"
        assert os.path.isfile(listing.path("layer_metrics", name + ".py")), f"{name}: no reader file"
        meta = listing.load("layer_metrics", name + ".json")
        assert meta["what"], name
        for key in READER_KEYS:
            assert per_layer[name][key] == meta[key], f"{name}: the entry's {key} is not its file's"
    for name, entry in per_layer.items():
        named = entry.get("workloads", listing.cells)
        assert (cell in named) == (name in names), (
            f"{name}: its workloads {'name' if cell in named else 'lack'} {cell}, "
            f"whose file {'lists' if name in names else 'does not list'} it"
        )
        assert named == [c for c in listing.cells if c in named], f"{name}: workloads {named} not in the cells' order"


def cell_is_its_files(listing: Listing, cell: str) -> None:
    """The cell's configuration and mix are entries and files, and the
    configuration's entry says what its file says."""
    w = listing.workload(cell)
    entry = listing.config_entry(w["config"])
    assert entry["file"] == f"benchmark/configs/{w['config']}.json", entry["file"]
    doc, mix = listing.deployment(cell), listing.mix(cell)
    assert doc["name"] == w["config"] and mix["name"] == w["traffic"]
    for key in ("source", "reduced"):
        assert entry[key] == doc[key], f"{w['config']}: the entry's {key} is not its file's"


def cell_lists_own_work_last(listing: Listing, cell: str) -> None:
    """PR 38's seventeen and the answers' bytes, in that order and together,
    last of what the benchmark had when PR 42 listed them: behind them only
    the hash readers, where the cell lists those, and then readers whose
    entries `per_layer` holds behind theirs (what a later PR appends)."""
    names, last = listing.lists[cell], [*own_work(), ANSWER]
    assert [n for n in names if n in last] == last, f"{cell}: the seventeen and {ANSWER}, in own_work.json's order"
    at = names.index(last[0])
    assert names[at : at + len(last)] == last, f"{cell}: a reader stands among the seventeen"
    behind = names[at + len(last) :]
    hashes = [n for n in behind if n in HASH_READERS]
    assert behind[: len(hashes)] == hashes, f"{cell}: {behind}"
    order = list(listing.per_layer)
    later = [n for n in behind[len(hashes) :] if n in order and order.index(n) < order.index(ANSWER)]
    assert not later, f"{cell}: {later} listed behind the seventeen, with entries before theirs"


def cell_lists_what_its_deployment_and_mix_make_due(listing: Listing, cell: str) -> None:
    """PR 31's five where a block carries 1,000 votes or more; the six of
    a changing set exactly where the mix carries `valset`; the four hash
    readers exactly where a block's txs are a device-sized tree, and then
    not the verify kernel's two."""
    from benchmark.lib.checks import DEVICE_MIN_LEAVES

    names, doc, mix = set(listing.lists[cell]), listing.deployment(cell), listing.mix(cell)
    if doc["validators"] >= 1000:
        assert set(PR31) <= names, f"{cell}: {sorted(set(PR31) - names)} not listed at {doc['validators']} validators"
    if "valset" in mix:
        assert set(VALSET_READERS) <= names, f"{cell}: {sorted(set(VALSET_READERS) - names)} not listed under a changing set"
    else:
        assert not set(VALSET_READERS) & names, f"{cell}: {sorted(set(VALSET_READERS) & names)} listed under a static set"
    if mix["txs"]["per_block"] >= DEVICE_MIN_LEAVES:
        assert set(HASH_READERS) <= names and not set(VERIFY_KERNEL) & names, f"{cell}: a device-sized tx tree a block"
    else:
        assert not set(HASH_READERS) & names, f"{cell}: {sorted(set(HASH_READERS) & names)} listed, and no tree is the device's"


CELL_RULES = [
    cell_and_readers_name_each_other, cell_is_its_files, cell_lists_own_work_last,
    cell_lists_what_its_deployment_and_mix_make_due,
]


def withheld_names() -> list[str]:
    """The deployments', mixes' and cells' files kept beside the tests
    (`own_work.json` is entries, not a file to list)."""
    return sorted(n for n in os.listdir(os.path.join(HERE, "withheld")) if n != "own_work.json")


def withheld_file_is_listed_as_it_is(listing: Listing, name: str) -> None:
    """A withheld file is either not listed, or listed byte for byte: a
    deployment's under `configs/`, a mix's under `traffic/`; a withheld
    cell, once listed, starts with the withheld list (readers that came
    later are appended) and says the rest as withheld."""
    kept = listing.load("tests", "withheld", name)
    if "layer_metrics" in kept:
        if name[: -len(".json")] not in listing.cells:
            return
        listed = listing.load("cells", name)
        assert {**listed, "layer_metrics": listed["layer_metrics"][: len(kept["layer_metrics"])]} == kept, name
        return
    where = "traffic" if "driver" in kept else "configs"
    if os.path.exists(listing.path(where, name)):
        assert listing.raw(where, name) == listing.raw("tests", "withheld", name), f"{where}/{name} is not the withheld file"


# -- a fifth cell, listed by additions alone -------------------------------------------------


def cell_to_add(tree: Listing) -> str | None:
    """The first withheld cell the tree does not list yet (none, once a
    later PR has listed both: the copy then differs by the made-up reader
    alone, in the tree's last cell)."""
    return next((cell for cell in WITHHELD_CELLS if cell not in tree.cells), None)


def cells_after_additions(tree: Listing) -> list[str]:
    added = cell_to_add(tree)
    return tree.cells + ([added] if added else [])


def list_by_additions(top, tree: Listing) -> Listing:
    """A copy of the benchmark under `top` with one more cell listed the
    way a `model_config` PR must list it: the withheld deployment's, mix's
    and cell's files copied, one `configs` and one `workloads` entry, the
    cell's name appended to the `workloads` of every reader it lists; and
    one made-up reader (file pair, entry at the END of `per_layer`, name at
    the END of the cell's list). No entry and no file that was there is
    edited but the readers' `workloads`."""
    top = str(top)
    shutil.copytree(tree.bench, os.path.join(top, "benchmark"), ignore=shutil.ignore_patterns("out", "cache", "__pycache__"))
    bench, b = os.path.join(top, "benchmark"), copy.deepcopy(tree.contract)
    cell = cell_to_add(tree)
    if cell is None:
        cell = tree.cells[-1]
    else:
        config, mix = cell.rsplit(".", 1)
        for where, name in (("configs", config), ("traffic", mix), ("cells", cell)):
            if not os.path.exists(os.path.join(bench, where, name + ".json")):
                shutil.copy(os.path.join(HERE, "withheld", name + ".json"), os.path.join(bench, where, name + ".json"))
        if config not in [c["name"] for c in b["configs"]]:
            doc = tree.load("tests", "withheld", config + ".json")
            b["configs"].append({"name": config, "source": doc["source"], "file": f"benchmark/configs/{config}.json",
                                 "reduced": doc["reduced"], "why": "a withheld deployment, listed in a rehearsal's copy"})
        b["workloads"].append({"name": cell, "config": config, "traffic": mix, "chips": 1,
                               "why": "a withheld cell, listed in a rehearsal's copy"})
    with open(os.path.join(bench, "cells", cell + ".json")) as f:
        doc = json.load(f)
    meta = {"name": MADE_UP, "unit": "count", "better": "lower", "source": "program_counter", "layer": "fast-sync",
            "moves": "catchup_blocks_per_s", "what": "nothing: a reader a rehearsal made up, which finds nothing to read"}
    with open(os.path.join(bench, "layer_metrics", MADE_UP + ".json"), "w") as f:
        json.dump(meta, f)
    with open(os.path.join(bench, "layer_metrics", MADE_UP + ".py"), "w") as f:
        f.write("def reduce(obs):\n    return None\n")
    b["per_layer"].append({**{k: meta[k] for k in READER_KEYS}, "workloads": []})
    doc["layer_metrics"].append(MADE_UP)
    with open(os.path.join(bench, "cells", cell + ".json"), "w") as f:
        json.dump(doc, f)
    for m in b["per_layer"]:
        if m["name"] in doc["layer_metrics"] and cell not in m["workloads"]:
            m["workloads"].append(cell)
    with open(os.path.join(top, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return Listing(top)
