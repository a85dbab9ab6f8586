"""Device mesh construction — the TPU-native communication substrate.

The reference's cross-node transport is Interconnect (SURVEY.md §2.2): TCP
sessions + virtual channels between every node pair. The TPU build splits
that into two planes (SURVEY.md §5.8): bulk data rides XLA collectives over
the ICI mesh (this module + ydb_tpu.parallel.dist/shuffle); control traffic
stays on the host actor shim (ydb_tpu.runtime).

Mesh axes used by the engine:
  * ``shard`` — table-partition parallelism (the DP axis): each device owns
    a horizontal slice; scans/aggregations fan out here, partial states
    merge with psum/pmin/pmax, shuffles ride all_to_all.
  * ``pipe``  — optional stage-pipelining axis for multi-stage dataflows
    (kept size 1 until the DQ stage graph spans it).

On real hardware the shard axis should map contiguously onto the physical
ring so psum/all_to_all ride ICI neighbor links; jax's default device order
on TPU slices already does this.
"""

from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh

SHARD_AXIS = "shard"
PIPE_AXIS = "pipe"


#: every engine call site maps over the mesh through this one name
shard_map = jax.shard_map


def make_mesh(
    n_shards: int | None = None,
    n_pipe: int = 1,
    devices=None,
) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    if n_shards is None:
        n_shards = len(devices) // n_pipe
    need = n_shards * n_pipe
    if need > len(devices):
        raise ValueError(
            f"mesh {n_shards}x{n_pipe} needs {need} devices, "
            f"have {len(devices)}"
        )
    arr = np.array(devices[:need]).reshape(n_shards, n_pipe)
    return Mesh(arr, (SHARD_AXIS, PIPE_AXIS))


def shard_axis(mesh: Mesh) -> str:
    return SHARD_AXIS
