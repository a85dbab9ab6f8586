"""Pallas TPU kernel for the group-by reduction above the one-hot tier.

The device group-by core is a sum by group id
(kernels.fused_group_reduce, the BlockCombineHashed analog,
mkql_block_agg.cpp:1637). XLA lowers `.at[idx].add` to a serialized
scatter on TPU; this module provides the classic TPU-native alternative
— tile the rows, expand each tile to a one-hot (rows x groups) matrix
in VMEM and contract it against every slot column with one MXU dot —
instead of round-tripping a scatter.

Numerics: float32 accumulates exactly what the scatter path would
(same adds, different order — fp addition reorders are inherent to any
parallel reduction). Other dtypes (int32, int64 decimals, float64) take
the scatter path, so results never silently lose precision. Group
counts <= MAX_GROUPS keep the one-hot tile in VMEM.

On by default on a TPU backend; YDB_TPU_PALLAS=0|1 overrides
(kernels.fused_group_reduce consults ``enabled()``). Tests run the same
kernel in interpreter mode on CPU and compile it for a described v5e
(tests/test_tpu_compile.py).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

ROW_TILE = 1024
MAX_GROUPS = 2048


#: test override: True/False forces the decision regardless of
#: env/backend (consulted at TRACE time — rebuild executors to switch)
FORCE: bool | None = None


def enabled() -> bool:
    if FORCE is not None:
        return FORCE
    v = os.environ.get("YDB_TPU_PALLAS")
    if v is not None:
        return v not in ("0", "", "off")
    return jax.default_backend() == "tpu"


#: fused multi-column kernel slot cap: the out tile is (groups x slots)
#: in VMEM next to the (ROW_TILE x groups) one-hot, so slots stay a
#: single 128-lane tile. Real programs stack well under this (TPC-H Q1
#: needs 5 int64 + 4 f64 + 6 count slots across all its banks).
MAX_FUSED_SLOTS = 128


def supported_fused(dtype, num_groups: int, n_slots: int) -> bool:
    """Eligibility of the fused multi-column tile kernel
    (kernels.fused_group_reduce's >ONEHOT tier). float32 only: its
    contraction is a ``tpu.matmul``, and Mosaic refuses an int32
    lhs/rhs on a v5e — int32 banks take the scatter tier, as int64
    banks do."""
    return (jnp.dtype(dtype) == jnp.float32 and num_groups <= MAX_GROUPS
            and n_slots <= MAX_FUSED_SLOTS)


def _pad_rows(a: jax.Array, n: int, fill):
    pad = (-a.shape[0]) % n
    if pad == 0:
        return a
    return jnp.concatenate(
        [a, jnp.full((pad,) + a.shape[1:], fill, dtype=a.dtype)])


@functools.partial(jax.jit, static_argnames=("num_groups", "interpret"))
def grouped_sum_multi(values: jax.Array, gid: jax.Array, num_groups: int,
                      interpret: bool = False) -> jax.Array:
    """Fused multi-column grouped sum: (rows x slots) values ->
    (num_groups x slots) per-group sums in ONE kernel.

    The group-by's >ONEHOT_GROUP_LIMIT tier: each row tile expands to a
    (ROW_TILE x groups) one-hot once and contracts against ALL slot
    columns with a single MXU dot. Rows with gid >= num_groups drop
    (callers encode dead and invalid rows that way).
    """
    from jax.experimental import pallas as pl

    k_pad = max(128, -(-num_groups // 128) * 128)
    n_slots = values.shape[1]
    s_pad = max(128, -(-n_slots // 128) * 128)
    vals = _pad_rows(values, ROW_TILE, 0)
    if s_pad != n_slots:
        vals = jnp.concatenate(
            [vals, jnp.zeros((vals.shape[0], s_pad - n_slots),
                             dtype=vals.dtype)], axis=1)
    gids = _pad_rows(gid.astype(jnp.int32), ROW_TILE, k_pad)
    tiles = vals.shape[0] // ROW_TILE
    vals3 = vals.reshape(tiles, ROW_TILE, s_pad)
    gids3 = gids.reshape(tiles, ROW_TILE, 1)

    def kernel(gid_ref, val_ref, out_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            out_ref[:, :] = jnp.zeros_like(out_ref)

        g = gid_ref[0, :, :]          # (ROW_TILE, 1)
        v = val_ref[0, :, :]          # (ROW_TILE, s_pad)
        groups = jax.lax.broadcasted_iota(
            jnp.int32, (ROW_TILE, k_pad), 1)
        onehot = (g == groups).astype(val_ref.dtype)
        # (ROW_TILE, k_pad)^T contracted with (ROW_TILE, s_pad) on the
        # row axis -> (k_pad, s_pad): one MXU pass covers every slot
        out_ref[:, :] += jax.lax.dot_general(
            onehot, v, (((0,), (0,)), ((), ())),
            preferred_element_type=out_ref.dtype)

    # the engine runs with jax_enable_x64; Mosaic cannot legalize the
    # implicit i64 index/constant types that mode introduces, and
    # nothing in this kernel needs 64 bits — trace it in 32-bit mode
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            grid=(tiles,),
            in_specs=[
                pl.BlockSpec((1, ROW_TILE, 1), lambda i: (i, 0, 0)),
                pl.BlockSpec((1, ROW_TILE, s_pad), lambda i: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((k_pad, s_pad), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((k_pad, s_pad), values.dtype),
            interpret=interpret,
        )(gids3, vals3)
    return out[:num_groups, :n_slots]
