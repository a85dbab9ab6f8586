"""Pallas group-by kernel: interpreter-mode equivalence with the
scatter path (compiled for a described v5e in test_tpu_compile.py)."""

import jax.numpy as jnp
import numpy as np
import pytest

from ydb_tpu.ssa import pallas_kernels
from ydb_tpu.ssa.kernels import scatter_sum


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_grouped_sum_matches_scatter(dtype):
    rng = np.random.default_rng(4)
    n, k = 3000, 37
    vals = jnp.asarray(rng.integers(0, 100, n), dtype=dtype)
    gid = jnp.asarray(rng.integers(0, k, n), dtype=jnp.int32)
    valid = jnp.asarray(rng.random(n) < 0.8)
    ref = scatter_sum(vals, valid, gid, k, dtype=dtype)
    got = pallas_kernels.scatter_sum_pallas(vals, valid, gid, k,
                                            dtype=dtype, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6)


def test_grouped_sum_edge_shapes():
    # non-multiple-of-tile row count, single group, empty-ish input
    vals = jnp.asarray(np.ones(5, dtype=np.float32))
    gid = jnp.asarray(np.zeros(5, dtype=np.int32))
    out = pallas_kernels.grouped_sum(vals, gid, 1, interpret=True)
    assert float(out[0]) == 5.0
    # all rows dropped (gid beyond num_groups)
    gid2 = jnp.asarray(np.full(5, 99, dtype=np.int32))
    out = pallas_kernels.grouped_sum(vals, gid2, 3, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), [0, 0, 0])


def test_gating():
    assert not pallas_kernels.supported(jnp.int64, 10)   # exactness
    assert not pallas_kernels.supported(jnp.float32, 10**6)  # VMEM
    assert pallas_kernels.supported(jnp.float32, 2048)


def test_scatter_sum_over_onehot_limit_with_pallas_off(monkeypatch):
    """Above ONEHOT_GROUP_LIMIT ``kernels.scatter_sum`` imports this
    module at trace time to ask ``enabled()``: with the Pallas path off
    (the CPU default) a 600-group sum must take the XLA scatter — the
    lazy import itself is what a broken module would fail."""
    from ydb_tpu.ssa import kernels

    monkeypatch.setattr(pallas_kernels, "FORCE", False)
    assert not pallas_kernels.enabled()
    rng = np.random.default_rng(7)
    n, k = 5000, 600
    assert k > kernels.ONEHOT_GROUP_LIMIT
    vals = rng.integers(0, 100, n)
    gid = rng.integers(0, k, n)
    valid = rng.random(n) < 0.8
    got = scatter_sum(jnp.asarray(vals, dtype=jnp.float32),
                      jnp.asarray(valid), jnp.asarray(gid, jnp.int32), k)
    want = np.bincount(gid[valid], weights=vals[valid], minlength=k)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
