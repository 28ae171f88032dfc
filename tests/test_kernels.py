"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps in interpret
mode (the kernel body runs in Python on CPU; BlockSpecs target TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sketch as cs
from repro.kernels import ref
from repro.kernels.cs_adam import cs_adam_fused
from repro.kernels.cs_query import cs_query
from repro.kernels.cs_update import cs_update
from repro.kernels import ops


def _addr(n, k, depth, width, seed, signed):
    from repro.core.hashing import HashFamily
    fam = HashFamily(seed=seed, depth=depth, width=width)
    ids = jnp.asarray(np.random.RandomState(seed).randint(0, n, size=k),
                      jnp.int32)
    return fam.bucket(ids), (fam.sign(ids) if signed else None), ids


SWEEP = [
    # (depth, width, dim, k, dtype)
    (1, 16, 128, 8, jnp.float32),
    (3, 16, 128, 32, jnp.float32),
    (3, 64, 256, 64, jnp.float32),
    (5, 32, 128, 16, jnp.float32),
    (3, 16, 128, 32, jnp.bfloat16),
]


@pytest.mark.parametrize("depth,width,dim,k,dtype", SWEEP)
@pytest.mark.parametrize("signed", [True, False])
def test_query_kernel_matches_ref(depth, width, dim, k, dtype, signed):
    S = jax.random.normal(jax.random.PRNGKey(1), (depth, width, dim)).astype(dtype)
    b, s, _ = _addr(1000, k, depth, width, seed=depth * 7 + k, signed=signed)
    got = cs_query(S, b, s, interpret=True)
    want = ref.cs_query_ref(S, b, s)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=5e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("depth,width,dim,k,dtype", SWEEP)
@pytest.mark.parametrize("signed", [True, False])
def test_update_kernel_matches_ref(depth, width, dim, k, dtype, signed):
    S = jax.random.normal(jax.random.PRNGKey(2), (depth, width, dim)).astype(dtype)
    b, s, _ = _addr(1000, k, depth, width, seed=depth * 13 + k, signed=signed)
    delta = jax.random.normal(jax.random.PRNGKey(3), (k, dim)).astype(dtype)
    got = cs_update(S, b, s, delta, interpret=True)
    want = ref.cs_update_ref(S, b, s, delta)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("depth,width,dim,k",
                         [(3, 16, 128, 8), (3, 64, 256, 32), (1, 32, 128, 16)])
@pytest.mark.parametrize("track_m", [True, False])
def test_fused_adam_kernel_matches_ref(depth, width, dim, k, track_m):
    kM = jax.random.PRNGKey(4)
    M = jax.random.normal(kM, (depth, width, dim)) if track_m else None
    V = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (depth, width, dim)))
    bm, sm, _ = _addr(500, k, depth, width, seed=11, signed=True)
    bv, _, _ = _addr(500, k, depth, width, seed=22, signed=False)
    g = jax.random.normal(jax.random.PRNGKey(6), (k, dim))
    kw = dict(lr=1e-3, b1=0.9, b2=0.999, eps=1e-8, bc1=0.1, bc2=0.001)
    Mo, Vo, u = cs_adam_fused(M, V, bm if track_m else None,
                              sm if track_m else None, bv, g,
                              interpret=True, **kw)
    Mr, Vr, ur = ref.adam_fused_ref(M, V, bm if track_m else None,
                                    sm if track_m else None, bv, g, **kw)
    if track_m:
        np.testing.assert_allclose(np.asarray(Mo), np.asarray(Mr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(Vo), np.asarray(Vr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(u), np.asarray(ur), atol=1e-5)


def test_fused_adam_streaming_semantics():
    """Duplicate ids: the fused kernel is STREAMING (later occurrences see
    earlier updates), matching the paper's per-item algorithm."""
    depth, width, dim = 3, 16, 128
    V = jnp.zeros((depth, width, dim))
    ids = jnp.zeros((4,), jnp.int32)
    from repro.core.hashing import HashFamily
    fam = HashFamily(seed=0, depth=depth, width=width)
    bv = fam.bucket(ids)
    g = jnp.ones((4, dim))
    kw = dict(lr=1.0, b1=0.9, b2=0.5, eps=0.0, bc1=1.0, bc2=1.0)
    _, Vo, _ = cs_adam_fused(None, V, None, None, bv, g, interpret=True, **kw)
    _, Vr, _ = ref.adam_fused_ref(None, V, None, None, bv, g, **kw)
    np.testing.assert_allclose(np.asarray(Vo), np.asarray(Vr), atol=1e-5)
    # v after 4 identical streaming updates of g²=1: 1-(1-b2)^4... via EMA
    v_expected = 1.0 - 0.5 ** 4
    got = float(Vo[0, bv[0, 0], 0])
    assert abs(got - v_expected) < 1e-5


def test_ops_dispatch_cpu_uses_ref():
    spec = cs.for_param((512, 64), compression=4.0, width_multiple=16)
    S = cs.init(spec)
    ids = jnp.arange(8, dtype=jnp.int32)
    out = ops.sketch_query(spec, S, ids)
    assert out.shape == (8, 64)
    out2 = ops.sketch_query(spec, S, ids, force="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=1e-6)
