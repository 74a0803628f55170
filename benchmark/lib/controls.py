"""Controls: the program with one guarantee broken, to show that `correct`
can come out false. Used by `benchmark/tests` and by the on-chip control
runs (`run.py --control <name>`); a benchmark run never installs one.

* `accept_all`: the process's verifier accepts every present signature, so
  a commit passes without 2/3 of the power in *valid* signatures.
* `host_answers` (in `drivers/catchup.py`): every commit-shaped batch is
  answered by the host library where a device answer is due.
* `host_trees` (`host_tree_hasher`): the node is built with the host tree
  hasher, so every Merkle tree, a block's 10,000 txs with them, is
  answered by the host library where a device tree is due.
* `static_reference` (in `lib/checks.py`): the sample is held to the
  genesis validator set at every height, as a node that never changed its
  set would serve it: false on a chain whose set changes, true on one
  whose set does not.
* `apphash_off_by_one` (in `drivers/catchup.py`): the record the node is
  held to has every app hash one height off, as a node that applied the
  wrong state would show.
"""

from __future__ import annotations

import numpy as np


def install_accept_all() -> None:
    from tendermint_tpu.services.verifier import BatchVerifier, set_default_verifier

    class AcceptAll(BatchVerifier):
        def verify_batch(self, triples):
            return np.ones(len(triples), dtype=bool)

        def verify_commits(self, pubkeys, commits, force_fused=None):
            return np.array(
                [[s is not None for s in sigs] for _msgs, sigs in commits], dtype=bool
            ).reshape(len(commits), len(pubkeys))

    set_default_verifier(AcceptAll())


def host_tree_hasher():
    from tendermint_tpu.services.hasher import TreeHasher

    return TreeHasher(backend="host")
