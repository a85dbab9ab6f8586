"""Pallas group-by kernel: interpreter-mode equivalence with the
scatter tier (compiled for a described v5e in test_tpu_compile.py)."""

import jax.numpy as jnp
import numpy as np
import pytest

from ydb_tpu.ssa import kernels, pallas_kernels


def _scatter_tier(vals, gid, k):
    out = jnp.zeros((k, vals.shape[1]), dtype=vals.dtype)
    return out.at[gid].add(vals, mode="drop")


@pytest.mark.parametrize("slots", [1, 6])
def test_grouped_sum_multi_matches_scatter(slots):
    rng = np.random.default_rng(4)
    n, k = 3000, 37
    vals = jnp.asarray(rng.integers(0, 100, (n, slots)),
                       dtype=jnp.float32)
    # ids in [0, k]: k is the drop slot of dead and invalid rows
    gid = jnp.asarray(rng.integers(0, k + 1, n), dtype=jnp.int32)
    got = pallas_kernels.grouped_sum_multi(vals, gid, k, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_scatter_tier(vals, gid, k)),
                               rtol=1e-6)


def test_grouped_sum_multi_edge_shapes():
    # non-multiple-of-tile row count, single group, one slot
    vals = jnp.asarray(np.ones((5, 1), dtype=np.float32))
    gid = jnp.asarray(np.zeros(5, dtype=np.int32))
    out = pallas_kernels.grouped_sum_multi(vals, gid, 1, interpret=True)
    assert out.shape == (1, 1) and float(out[0, 0]) == 5.0
    # all rows dropped (gid beyond num_groups)
    gid2 = jnp.asarray(np.full(5, 99, dtype=np.int32))
    out = pallas_kernels.grouped_sum_multi(vals, gid2, 3, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), [[0], [0], [0]])


def test_gating():
    assert not pallas_kernels.supported_fused(jnp.int64, 10, 4)  # exactness
    assert not pallas_kernels.supported_fused(jnp.int32, 10, 4)  # Mosaic
    assert not pallas_kernels.supported_fused(
        jnp.float32, 10**6, 4)  # VMEM
    assert not pallas_kernels.supported_fused(
        jnp.float32, 2048, pallas_kernels.MAX_FUSED_SLOTS + 1)
    assert pallas_kernels.supported_fused(jnp.float32, 2048, 4)


def test_group_reduce_over_onehot_limit_with_pallas_off(monkeypatch):
    """Above ONEHOT_GROUP_LIMIT ``kernels.fused_group_reduce`` imports
    this module at trace time to ask ``enabled()``: with the Pallas path
    off (the CPU default) a 600-group sum must take the XLA scatter —
    the lazy import itself is what a broken module would fail."""
    monkeypatch.setattr(pallas_kernels, "FORCE", False)
    assert not pallas_kernels.enabled()
    rng = np.random.default_rng(7)
    n, k = 5000, 600
    assert k > kernels.ONEHOT_GROUP_LIMIT
    vals = rng.integers(0, 100, n)
    gid = rng.integers(0, k + 1, n)
    got = kernels.fused_group_reduce(
        jnp.asarray(vals, dtype=jnp.float32)[:, None],
        jnp.asarray(gid, jnp.int32), k)
    live = gid < k
    want = np.bincount(gid[live], weights=vals[live], minlength=k)
    np.testing.assert_allclose(np.asarray(got)[:, 0], want, rtol=1e-6)
