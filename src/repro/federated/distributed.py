"""Multi-process federation support (``jax.distributed`` execution).

One federated run can span several jax processes — one per host (or, in
the CPU smoke tests, several local processes each owning a slice of
forced host devices). The mesh is GLOBAL: every process constructs the
identical ``(silo[, model])`` mesh over ``jax.devices()`` and runs the
identical compiled round (SPMD), but each process *owns* the silo rows
that live on its local devices:

  * device-resident silo state (η_{L_j}, optimizer moments, strategy
    state, the data shard) exists only on the owning process — privacy
    by placement extends across hosts;
  * host I/O is routed through the owner: checkpoint shards for silo j
    are written and read only by j's owner
    (:func:`owned_rows` / :func:`host_rows`);
  * control-plane values every process must agree on (scheduler masks,
    round keys, metering counts) are pure functions of (seed, absolute
    round), so each process recomputes them identically — zero
    cross-host control traffic, the same determinism contract bit-exact
    resume already relies on.

CPU processes need the gloo collectives backend, selected BEFORE
``jax.distributed.initialize`` — :func:`initialize` owns that ordering.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Optional

import jax
import numpy as np

PyTree = Any

# Environment schema for CLI-driven multi-process launches.
ENV_COORD = "REPRO_COORDINATOR"
ENV_NUM_PROCS = "REPRO_NUM_PROCESSES"
ENV_PROC_ID = "REPRO_PROCESS_ID"


def initialize(coordinator: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """``jax.distributed.initialize`` with the CPU collectives fixed up.

    Arguments default to the ``REPRO_COORDINATOR`` /
    ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID`` environment schema
    (what the CLI's ``--coordinator``/... flags export). On a CPU-only
    platform the default collectives backend cannot run multi-process
    computations at all; gloo can, and must be selected before the
    distributed client starts.
    """
    coordinator = coordinator or os.environ.get(ENV_COORD)
    if num_processes is None and os.environ.get(ENV_NUM_PROCS):
        num_processes = int(os.environ[ENV_NUM_PROCS])
    if process_id is None and os.environ.get(ENV_PROC_ID):
        process_id = int(os.environ[ENV_PROC_ID])
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )


def is_multiprocess() -> bool:
    return jax.process_count() > 1


def replicated(x, mesh):
    """Host value → global array replicated over the whole mesh."""
    from jax.sharding import NamedSharding, PartitionSpec

    host = np.asarray(jax.device_get(x))
    return jax.make_array_from_callback(
        host.shape, NamedSharding(mesh, PartitionSpec()),
        lambda idx: host[idx])


def globalize(tree: PyTree, mesh, pspec) -> PyTree:
    """Host-replicated pytree → global arrays sharded as ``pspec``.

    Every process passes the SAME host values (they are deterministic
    functions of the spec); ``make_array_from_callback`` materializes
    only this process's addressable shards, so a silo-sharded leaf
    costs each host only its own rows. Leaves that already are global
    arrays with this sharding pass through unchanged.
    """
    from jax.sharding import NamedSharding

    sharding = NamedSharding(mesh, pspec)

    def leaf(x):
        if isinstance(x, jax.Array) and x.sharding == sharding:
            return x
        host = np.asarray(jax.device_get(x))
        return jax.make_array_from_callback(
            host.shape, sharding, lambda idx: host[idx])

    return jax.tree_util.tree_map(leaf, tree)


def row_owner_process(mesh, row: int, rows_total: int) -> int:
    """Process index owning padded silo row ``row`` of ``rows_total``.

    Rows shard over the ``silo`` axis in equal contiguous blocks; the
    owner is the process of the block's device (first model column on a
    2-D mesh — the whole row of model columns is co-hosted per process
    under the contiguous device layout ``build_mesh`` produces).
    """
    devs = np.asarray(mesh.devices)
    n_blocks = mesh.shape["silo"]
    block = row // (rows_total // n_blocks)
    dev = devs[block] if devs.ndim == 1 else devs[block, 0]
    return int(dev.process_index)


def owned_rows(mesh, rows_total: int) -> list:
    """Padded-row indices this process owns (contiguous silo blocks)."""
    me = jax.process_index()
    return [r for r in range(rows_total)
            if row_owner_process(mesh, r, rows_total) == me]


def silo_sharded_from_rows(like: PyTree, mesh, rows: Dict[int, PyTree]) -> PyTree:
    """Owner-held row trees → a global silo-sharded stacked tree.

    ``like`` supplies shape/dtype (leading axis J_pad); ``rows`` maps
    padded-row index → that row's host tree and need only contain THIS
    process's owned real rows — ``make_array_from_callback`` asks each
    process for its addressable shards alone. Missing rows (padded
    dummies, rows owned elsewhere) fill with zeros: padded rows are
    permanently masked, and remote rows materialize on their owners.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    leaves, treedef = jax.tree_util.tree_flatten(like)
    row_leaves = {j: jax.tree_util.tree_flatten(t)[0] for j, t in rows.items()}

    def build(i, leaf):
        shape, dtype = leaf.shape, leaf.dtype

        def cb(idx):
            sl = idx[0] if idx else slice(0, shape[0])
            start = 0 if sl.start is None else sl.start
            stop = shape[0] if sl.stop is None else sl.stop
            out = np.zeros((stop - start,) + shape[1:], dtype)
            for r in range(start, stop):
                if r in row_leaves:
                    out[r - start] = np.asarray(row_leaves[r][i])
            return out

        return jax.make_array_from_callback(
            shape, NamedSharding(mesh, PartitionSpec("silo")), cb)

    return jax.tree_util.tree_unflatten(
        treedef, [build(i, leaf) for i, leaf in enumerate(leaves)])


def host_rows(x, rows: list) -> Dict[int, np.ndarray]:
    """{row index: host value} for owned rows of a silo-sharded global.

    Reads only this process's addressable shards — never triggers a
    cross-process collective (plain ``x[j]`` on a global array would
    dispatch one, deadlocking per-process checkpoint I/O).
    """
    out: Dict[int, np.ndarray] = {}
    want = set(rows)
    for shard in x.addressable_shards:
        sl = shard.index[0] if shard.index else slice(0, x.shape[0])
        data = np.asarray(shard.data)
        start = sl.start or 0
        for i in range(data.shape[0]):
            if start + i in want and start + i not in out:
                out[start + i] = data[i]
    return out
