"""Multi-host data plane: the jax.distributed-over-DCN seam
(SURVEY.md §5.8).

The reference scales its data plane with NCCL/MPI-style backends; the
TPU-native equivalent is a PROCESS-SPANNING `jax.sharding.Mesh`: every
host runs this same program, `jax.distributed.initialize` wires the
hosts into one runtime, and the existing `shard_map` steps in
`parallel/mesh.py` (batch-sharded generic verify, validator-sharded
table verify, psum power tallies) compile unchanged over the global
mesh — XLA routes the collectives over ICI within a slice and DCN
across hosts. Nothing in the verification code is single-host-specific;
this module is the composition seam:

    # on every host (same code, per-host coordinator/process args):
    from tendermint_tpu.parallel import distributed as dist
    dist.initialize(coordinator="host0:8476", num_processes=4,
                    process_id=<rank>)
    mesh = dist.global_batch_mesh()          # all chips on all hosts
    step = sharded_tables_verify_and_tally(mesh)
    ...                                      # identical from here on

Host-side inputs must be GLOBAL arrays: use `host_local_to_global` to
assemble a jax.Array from per-host shards (each host supplies only the
lanes of its own validators — the same shard-major layout
`shard_lanes_validator_major` produces).

There is no multi-host hardware here: tier-1 drives this seam in one
process (`tests/test_services.py`), the slow suite in two CPU processes
over `jax.distributed` (`tests/test_multiprocess.py`); it has never run
across hosts or on a chip. The mesh/step code it feeds is the same code
the 8-device virtual mesh and the driver's multichip dryrun run.
"""

from __future__ import annotations

import numpy as np

_init_mode: str | None = None  # None | "local" | "multi"


def initialize(
    coordinator: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Wire this process into the multi-host runtime.

    No-op when called with no arguments in a single-process setup (the
    common test/bench path), so call sites can run the same code on one
    host or many. Idempotent for the SAME mode; a multi-host request
    after a local init raises instead of silently running local-only
    (every host would otherwise verify just its own shard while
    believing the mesh is global)."""
    global _init_mode
    want_multi = coordinator is not None or (
        num_processes is not None and num_processes > 1
    )
    if _init_mode is not None:
        if want_multi and _init_mode == "local":
            raise RuntimeError(
                "distributed.initialize: already initialized single-process; "
                "multi-host init must happen before any local initialize()"
            )
        return
    if not want_multi:
        _init_mode = "local"  # single-process: nothing to wire
        return
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    _init_mode = "multi"


def global_batch_mesh():
    """1-D mesh over EVERY device of EVERY process (jax.devices() is
    global after jax.distributed.initialize)."""
    from tendermint_tpu.parallel.mesh import batch_mesh

    return batch_mesh()


def process_info() -> tuple[int, int]:
    """(process_index, process_count) — (0, 1) when single-process."""
    import jax

    return jax.process_index(), jax.process_count()


def host_local_to_global(mesh, spec, host_local: np.ndarray):
    """Assemble a global jax.Array from this host's shard.

    `host_local` is the slice of the global array this process owns
    under `spec` (e.g. its own validators' lanes in shard-major order).
    Single-process meshes just device_put with the sharding — the SAME
    call works in both topologies, which is what makes the step
    functions topology-agnostic."""
    import jax
    from jax.sharding import NamedSharding

    sharding = NamedSharding(mesh, spec)
    if jax.process_count() == 1:
        return jax.device_put(host_local, sharding)
    # multi-host: each process contributes its addressable shards; a
    # fully-replicated spec means every host holds the whole array
    global_shape = list(host_local.shape)
    axis = next((i for i, name in enumerate(spec) if name is not None), None)
    if axis is not None:
        # assumes the sharded axis is split exactly process_count ways
        # (one contiguous block per host — the layout
        # shard_lanes_validator_major produces); other layouts must call
        # jax.make_array_from_process_local_data themselves
        global_shape[axis] *= jax.process_count()
    return jax.make_array_from_process_local_data(
        sharding, host_local, tuple(global_shape)
    )
