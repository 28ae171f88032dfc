"""The kernels the chip runs vs their pure-jnp oracles, through the
registry: ``cs_ema_tiled`` (``update_read``) and ``cs_adam_tiled``
(``adam_rows``) under the Pallas interpreter (the kernel body runs in
Python on CPU; BlockSpecs and DMAs as on a TPU), the off-chip ``xla``
default against the per-item ``ref``, and the registry's rows.

Every batch here is collision-free — unique ids, and no two ids share a
bucket in any hash row — so batch, tiled and per-item semantics agree
(DESIGN.md §10); tests/test_backends.py covers colliding batches.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels as K
from repro.core import optimizers as O
from repro.core import sketch as cs
from repro.kernels import ref, registry


def _collision_free_ids(specs, k, seed):
    """``k`` distinct ids whose buckets are distinct in every hash row of
    every spec (greedy over a shuffled id range; needs width >= 2k or so
    at the depths used here)."""
    rng = np.random.RandomState(seed)
    cand = rng.permutation(1 << 16).astype(np.int32)
    buckets = [np.asarray(s.family.bucket(jnp.asarray(cand))) for s in specs]
    used = [[set() for _ in range(s.depth)] for s in specs]
    out = []
    for c in range(cand.shape[0]):
        hit = [[b[j, c] for j in range(b.shape[0])] for b in buckets]
        if any(h in u for hs, us in zip(hit, used) for h, u in zip(hs, us)):
            continue
        for hs, us in zip(hit, used):
            for h, u in zip(hs, us):
                u.add(h)
        out.append(cand[c])
        if len(out) == k:
            return jnp.asarray(np.array(out), jnp.int32)
    raise AssertionError("no collision-free id set at this width")


def _spec(depth, width, dim, *, signed, seed, dtype=jnp.float32):
    return cs.SketchSpec(depth=depth, width=width, dim=dim, signed=signed,
                         seed=seed, dtype=jnp.dtype(dtype))


SWEEP = [
    # (depth, width, dim, k, dtype); width >= 2k so that k ids with
    # distinct buckets in every hash row exist
    (1, 16, 128, 8, jnp.float32),
    (3, 64, 128, 32, jnp.float32),
    (3, 128, 256, 64, jnp.float32),
    (5, 32, 128, 16, jnp.float32),
    (3, 64, 128, 32, jnp.bfloat16),
]


@pytest.mark.parametrize("depth,width,dim,k,dtype", SWEEP)
@pytest.mark.parametrize("signed", [True, False])
@pytest.mark.parametrize("form", ["ema", "sum"])
def test_update_read_kernel_matches_ref(depth, width, dim, k, dtype, signed,
                                        form):
    """``cs_ema_tiled`` (the 'interpret' backend) equals 'ref' on a
    collision-free batch: sketch and estimate, signed rows as a
    Count-Sketch and unsigned as a Count-Min, bf16 cells included — for
    Adam's masked EMA (β 0.9, scale 0.1) and Adagrad's unmasked sum
    (β 1, scale 1)."""
    spec = _spec(depth, width, dim, signed=signed, seed=depth * 7 + k,
                 dtype=dtype)
    rng = np.random.RandomState(depth * 13 + k)
    S = jnp.asarray(rng.randn(depth, width, dim), jnp.float32)
    if not signed:
        S = jnp.abs(S)
    S = S.astype(dtype)
    ids = _collision_free_ids([spec], k, seed=k)
    x = jnp.asarray(rng.randn(k, dim), jnp.float32)
    if form == "ema":
        kw = dict(beta=0.9, scale=0.1,
                  mask=jnp.asarray(rng.rand(k, 1) > 0.25, jnp.float32))
    else:
        kw = dict(beta=1.0, scale=1.0)
    want = K.update_read(spec, S, ids, x, backend="ref", **kw)
    got = K.update_read(spec, S, ids, x, backend="interpret", **kw)
    assert got[0].dtype == S.dtype
    for a, b in zip(want, got):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)


def _adam_case(depth, width, dim, k, track_m, seed):
    spec_m = _spec(depth, width, dim, signed=True, seed=10 + seed)
    spec_v = _spec(depth, width, dim, signed=False, seed=20 + seed)
    rng = np.random.RandomState(seed)
    M = jnp.asarray(rng.randn(depth, width, dim), jnp.float32) \
        if track_m else None
    V = jnp.abs(jnp.asarray(rng.randn(depth, width, dim), jnp.float32))
    ids = _collision_free_ids([spec_m, spec_v] if track_m else [spec_v], k,
                              seed=seed)
    g = jnp.asarray(rng.randn(k, dim), jnp.float32)
    return (spec_m if track_m else None), spec_v, M, V, ids, g


def _adam_rows(backend, spec_m, spec_v, M, V, ids, g, b1):
    return K.adam_rows(spec_m, spec_v, M, V, ids, g, jnp.asarray(2, jnp.int32),
                       lr=1e-2, b1=b1, b2=0.999, eps=1e-8, backend=backend)


@pytest.mark.parametrize("depth,width,dim,k",
                         [(3, 16, 128, 8), (3, 64, 256, 32), (1, 32, 128, 16)])
@pytest.mark.parametrize("track_m", [True, False])
def test_fused_adam_kernel_matches_ref(depth, width, dim, k, track_m):
    """``cs_adam_tiled`` (the 'interpret' pair backend, dedup pre-pass
    included) equals the per-item oracle ``ref.adam_fused_ref`` fed the
    same addressing on a collision-free batch."""
    spec_m, spec_v, M, V, ids, g = _adam_case(depth, width, dim, k, track_m,
                                              seed=depth + k)
    b1 = 0.9 if track_m else 0.0
    got = _adam_rows("interpret", spec_m, spec_v, M, V, ids, g, b1)
    bm = spec_m.family.bucket(ids) if track_m else None
    sm = spec_m.family.sign(ids) if track_m else None
    want = ref.adam_fused_ref(M, V, bm, sm, spec_v.family.bucket(ids), g,
                              lr=1e-2, b1=b1, b2=0.999, eps=1e-8,
                              bc1=1.0 - b1 ** 2, bc2=1.0 - 0.999 ** 2)
    for a, b in zip(want, got):
        if a is None:
            assert b is None
            continue
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("depth", [1, 3])
@pytest.mark.parametrize("track_m", [True, False])
def test_xla_matches_ref_collision_free(depth, track_m):
    """The off-chip default ('xla': dedup + one vectorized batch step)
    and the exact per-item semantics ('ref') agree to float32 rounding
    when no two ids share a bucket.  Not bit for bit: XLA:CPU contracts
    the per-item scan's ``M + s·Δm`` into one FMA and the batch step's
    not, so a sketch cell can differ in its last bit (observed at most
    2.4e-7 on cells of unit scale, 1.5e-8 on update rows)."""
    case = _adam_case(depth, 64, 128, 16, track_m, seed=depth)
    b1 = 0.9 if track_m else 0.0
    want = _adam_rows("ref", *case, b1)
    got = _adam_rows("xla", *case, b1)
    for a, b in zip(want, got):
        if a is None:
            assert b is None
            continue
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.parametrize("kind,op", [("pair", "adam_rows"),
                                     ("sketch", "update_read"),
                                     ("countmin", "update_read")])
def test_kernel_rows_hold_one_backend_per_platform(kind, op):
    """Each kernelized op registers its oracle, the off-chip and the chip
    implementation, and the interpreter the tests run — nothing else —
    and None/'auto' picks this host's implementation."""
    assert registry.backends(kind, op) == ("ref", "xla", "tiled",
                                           "interpret")
    want = "tiled" if jax.default_backend() == "tpu" else "xla"
    assert registry.resolve(kind, op, None) == want
    assert registry.resolve(kind, op, "auto") == want


@pytest.mark.parametrize("path", ["sparse_rows", "dense_store"])
def test_unknown_backend_raises(path):
    """A backend name the registry does not hold — the removed 'stream'
    or a typo — raises ``KeyError`` when the step traces, on the sparse
    rows path and on a dense sketched store alike."""
    from repro.train.steps import make_sparse_embedding_step
    n, d = 64, 128
    for name in ("stream", "tiledd"):
        hp = O.SketchHParams(compression=2.0, width_multiple=16,
                             backend=name)
        if path == "sparse_rows":
            init_fn, step_fn, opt = make_sparse_embedding_step(
                n, d, hparams=hp)
            table = init_fn(jax.random.PRNGKey(0))
            ids = jnp.arange(8, dtype=jnp.int32)
            with pytest.raises(KeyError, match=name):
                step_fn(table, opt.init(), ids, jnp.ones((8, d)))
        else:
            params = {"table": jnp.ones((n, d))}
            opt = O.countsketch_adam(1e-2, policy=lambda p, shape: True,
                                     hparams=hp)
            with pytest.raises(KeyError, match=name):
                opt.update(params, opt.init(params), params)
