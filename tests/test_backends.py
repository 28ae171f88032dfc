"""Cross-backend parity: ref | xla | tiled | interpret (DESIGN.md §10).

Two tiers of agreement, from exact to statistical:

  1. tiled == ref on COLLISION-FREE batches (the dedup-equivalence
     argument: once ids are unique and no two ids share a sketch bucket,
     batch and per-item semantics coincide bit-for-bit);
  2. on colliding batches tiled implements "batch within a tile,
     streaming across tiles" — asserted EXACTLY against a jnp oracle of
     that semantics, and within tolerance against ref (the residual is
     median/min estimator noise, quantified here with fixed seeds).

The Pallas kernel runs under the interpreter on CPU, named explicitly:
the 'interpret' backend, or ``interpret=True`` on the kernel (kernel
body in Python, BlockSpecs/DMAs as on TPU).  'tiled' itself only
compiles for a TPU (tests/test_chip_compile.py).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels as K
from repro.core import sketch as cs
from repro.kernels import dedup as dd, ref, registry


LR = dict(lr=1e-2, b1=0.9, b2=0.999, eps=1e-8)


def _specs(n, d, depth, *, compression=4.0, width_multiple=16, seed=0,
           identity=False):
    mk = functools.partial(cs.for_param, (n, d), compression=compression,
                           depth=depth, width_multiple=width_multiple,
                           identity=identity)
    return (mk(signed=True, seed=10 + seed), mk(signed=False, seed=20 + seed))


def _states(spec_m, spec_v, track_m, seed=0):
    rng = np.random.RandomState(seed)
    M = jnp.asarray(rng.randn(*spec_m.shape), jnp.float32) if track_m else None
    V = jnp.abs(jnp.asarray(rng.randn(*spec_v.shape), jnp.float32))
    return M, V


def _applied(n, d, ids, upd):
    out = np.zeros((n, d), np.float32)
    np.add.at(out, np.asarray(ids), np.asarray(upd))
    return out


def _run(backend, spec_m, spec_v, M, V, ids, g, step=2, **kw):
    kw = {**LR, **kw}
    return K.adam_rows(spec_m if M is not None else None, spec_v,
                       M, V, ids, g, jnp.asarray(step, jnp.int32),
                       backend=backend, **kw)


def test_registry_contents():
    assert registry.backends("pair", "adam_rows") == (
        "ref", "xla", "tiled", "interpret")
    assert registry.resolve("pair", "adam_rows", "tiled") == "tiled"
    # auto resolves per host: tiled on TPU, the vectorized jnp path off it
    assert registry.resolve("pair", "adam_rows", None) == (
        "tiled" if jax.default_backend() == "tpu" else "xla")
    with pytest.raises(KeyError):
        registry.resolve("pair", "adam_rows", "nope")


@pytest.mark.parametrize("depth", [1, 3])
@pytest.mark.parametrize("track_m", [True, False])
def test_tiled_matches_per_item_oracle_collision_free(depth, track_m):
    """Identity hashing (bucket = id, width >= n) + unique ids: a
    collision-free batch, where tiled must equal ``ref.adam_fused_ref``
    (the per-item oracle) exactly — the acceptance bar of DESIGN.md §10."""
    n, d, k = 64, 128, 16
    spec_m, spec_v = _specs(n, d, depth, identity=True, seed=depth)
    M, V = _states(spec_m, spec_v, track_m, seed=depth)
    rng = np.random.RandomState(depth + 5)
    ids = jnp.asarray(rng.permutation(n)[:k], jnp.int32)  # unique
    g = jnp.asarray(rng.randn(k, d), jnp.float32)
    b1 = 0.9 if track_m else 0.0
    r = _run("ref", spec_m, spec_v, M, V, ids, g, b1=b1)
    for backend in ("xla", "interpret"):
        t = _run(backend, spec_m, spec_v, M, V, ids, g, b1=b1)
        for a, b in zip(r, t):
            if a is None:
                assert b is None
                continue
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)


@pytest.mark.parametrize("depth", [1, 3])
def test_tiled_matches_ref_real_hash_no_bucket_collisions(depth):
    """Real multiply-shift hashing, fixed seed VERIFIED collision-free for
    these ids — exact agreement again (the equivalence does not depend on
    identity mode)."""
    n, d, k = 4096, 128, 8
    spec_m, spec_v = _specs(n, d, depth, compression=2.0,
                            width_multiple=256, seed=depth)
    rng = np.random.RandomState(depth)
    ids = jnp.asarray(rng.choice(n, k, replace=False), jnp.int32)
    for spec in (spec_m, spec_v):
        b = np.asarray(spec.family.bucket(ids))
        assert all(len(set(b[j])) == k for j in range(depth)), \
            "precondition: pick a seed with no bucket collisions"
    M, V = _states(spec_m, spec_v, True, seed=depth)
    g = jnp.asarray(rng.randn(k, d), jnp.float32)
    r = _run("ref", spec_m, spec_v, M, V, ids, g)
    t = _run("interpret", spec_m, spec_v, M, V, ids, g)
    for a, b in zip(r, t):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def _tile_batch_oracle(M, V, bm, sm, bv, g, *, lr, b1, b2, eps, bc1, bc2,
                       tile, n_valid):
    """jnp reference of the tiled semantics: batch within a tile,
    streaming across tiles."""
    k, _ = g.shape
    track_m = M is not None
    upds = []
    for t0 in range(0, k, tile):
        sl = slice(t0, t0 + tile)
        valid = (np.arange(t0, t0 + tile) < n_valid).astype(
            np.float32)[:, None]
        gc = g[sl]
        if track_m:
            m_old = ref.cs_query_ref(M, bm[:, sl], sm[:, sl])
            dm = (1 - b1) * (gc - m_old) * valid
            M = ref.cs_update_ref(M, bm[:, sl], sm[:, sl], dm)
            mhat = (m_old + dm) / bc1
        else:
            mhat = gc
        v_old = ref.cs_query_ref(V, bv[:, sl], None)
        dv = (1 - b2) * (gc * gc - v_old) * valid
        V = ref.cs_update_ref(V, bv[:, sl], None, dv)
        v_new = jnp.maximum(v_old + dv, 0.0)
        upds.append(valid * (-lr) * mhat / (jnp.sqrt(v_new / bc2) + eps))
    return M, V, jnp.concatenate(upds)


@pytest.mark.parametrize("depth", [1, 3])
@pytest.mark.parametrize("track_m", [True, False])
def test_tiled_exact_vs_its_oracle_under_collisions(depth, track_m):
    """Heavy bucket collisions (32 unique ids, 16-wide sketch): the tiled
    kernel must still match its own semantics EXACTLY — the intra-tile
    equality-matrix accumulation and the cross-tile streaming are not
    allowed to lose or double-count mass."""
    from repro.kernels.cs_adam_tiled import cs_adam_tiled
    width, d, k, tile = 16, 128, 32, 8
    rng = np.random.RandomState(depth)
    M = jnp.asarray(rng.randn(depth, width, d), jnp.float32) \
        if track_m else None
    V = jnp.abs(jnp.asarray(rng.randn(depth, width, d), jnp.float32))
    bm = jnp.asarray(rng.randint(0, width, (depth, k)), jnp.int32)
    bv = jnp.asarray(rng.randint(0, width, (depth, k)), jnp.int32)
    sm = jnp.asarray(rng.choice([-1.0, 1.0], (depth, k)), jnp.float32)
    g = jnp.asarray(rng.randn(k, d), jnp.float32)
    kw = dict(lr=1e-2, b1=0.9 if track_m else 0.0, b2=0.999, eps=1e-8,
              bc1=0.19, bc2=0.002)
    got = cs_adam_tiled(M, V, bm if track_m else None,
                        sm if track_m else None, bv, g, interpret=True,
                        tile=tile, n_valid=k - 3, **kw)
    want = _tile_batch_oracle(M, V, bm if track_m else None,
                              sm if track_m else None, bv, g,
                              tile=tile, n_valid=k - 3, **kw)
    for a, b in zip(got, want):
        if b is None or (track_m is False and a is None):
            continue
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.parametrize("depth", [1, 3])
def test_tiled_vs_ref_tolerance_under_collisions(depth):
    """Colliding batches: streaming (ref) and tiled legitimately differ by
    estimator noise.  Fixed seeds; the applied parameter delta must stay
    within the empirically calibrated envelope (observed max 0.47)."""
    n, d, k = 4096, 64, 32
    worst = 0.0
    for seed in range(4):
        spec_m, spec_v = _specs(n, d, depth, compression=16.0,
                                width_multiple=64, seed=seed)
        M, V = cs.init(spec_m), cs.init(spec_v)
        rng = np.random.RandomState(seed)
        ids = jnp.asarray(rng.choice(n, k, replace=False), jnp.int32)
        g = jnp.asarray(rng.randn(k, d), jnp.float32)
        _, _, ur = _run("ref", spec_m, spec_v, M, V, ids, g)
        _, _, ut = _run("interpret", spec_m, spec_v, M, V, ids, g)
        ar, at = _applied(n, d, ids, ur), _applied(n, d, ids, ut)
        worst = max(worst, np.linalg.norm(ar - at) / np.linalg.norm(ar))
    assert worst < 0.6, worst


@pytest.mark.parametrize("backend", ["interpret", "xla"])
def test_dedup_backends_apply_duplicates_exactly_once(backend):
    """Duplicate-heavy batch in identity mode: the dedup backends must
    apply, per id, exactly the update of the segment-summed gradient —
    equal to ref run on the pre-merged batch."""
    n, d = 64, 128
    spec_m, spec_v = _specs(n, d, 3, identity=True)
    M, V = _states(spec_m, spec_v, True)
    rng = np.random.RandomState(0)
    ids_np = rng.randint(0, 8, 24)                       # ~3× multiplicity
    ids = jnp.asarray(ids_np, jnp.int32)
    g = jnp.asarray(rng.randn(24, d), jnp.float32)
    _, _, ut = _run(backend, spec_m, spec_v, M, V, ids, g)
    # oracle: merge duplicates first, then the per-item algorithm
    b = dd.dedup_rows(ids, g)
    nu = int(b.n_unique)
    _, _, um = _run("ref", spec_m, spec_v, M, V,
                    b.unique_ids[:nu], b.rows[:nu])
    a_t = _applied(n, d, ids, ut)
    a_m = _applied(n, d, b.unique_ids[:nu], um)
    np.testing.assert_allclose(a_t, a_m, atol=1e-5)


@pytest.mark.parametrize("backend", ["interpret", "xla"])
def test_empty_batch_is_identity(backend):
    n, d = 128, 128
    spec_m, spec_v = _specs(n, d, 3)
    M, V = _states(spec_m, spec_v, True)
    ids = jnp.zeros((0,), jnp.int32)
    g = jnp.zeros((0, d), jnp.float32)
    Mo, Vo, u = _run(backend, spec_m, spec_v, M, V, ids, g)
    assert u.shape == (0, d)
    np.testing.assert_array_equal(np.asarray(Mo), np.asarray(M))
    np.testing.assert_array_equal(np.asarray(Vo), np.asarray(V))


def test_sparse_rows_adam_routes_backends():
    """optimizer-level entry point: the named backend is the one that
    runs, and on a collision-free batch (identity hashing, unique ids)
    'interpret' (the tiled kernel under the interpreter) and 'xla' give
    the same (table, state) trajectory."""
    from repro.core import optimizers as O
    n, d = 512, 128
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.permutation(n)[:16], jnp.int32)
    rows = jnp.asarray(rng.randn(16, d), jnp.float32)
    outs = []
    for backend in ("interpret", "xla"):
        hp = O.SketchHParams(compression=1.0, width_multiple=16,
                             identity=True, backend=backend)
        opt = O.sparse_rows_adam(1e-2, shape=(n, d), hparams=hp)
        state = opt.init()
        with registry.recording() as rec:
            upd, state = opt.update({"ids": ids, "rows": rows}, state)
        assert {r[2] for r in rec} == {backend}
        table = O.apply_sparse_updates(jnp.zeros((n, d)), upd)
        outs.append((np.asarray(table), np.asarray(state["v"])))
    np.testing.assert_allclose(outs[0][0], outs[1][0], atol=1e-6)
    np.testing.assert_allclose(outs[0][1], outs[1][1], atol=1e-6)


@pytest.mark.parametrize("kernel", ["adam", "ema"])
def test_tiled_split_into_calls_matches_one_call(kernel, monkeypatch):
    """A batch longer than one call's SMEM address budget runs as a scan
    of calls; call c+1 sees call c's writes exactly as tile t+1 sees tile
    t's, so the split result equals the one-call result bit-for-bit
    (heavy collisions, ragged n_valid)."""
    from repro.kernels import row_groups as rg
    from repro.kernels.cs_adam_tiled import cs_adam_tiled
    from repro.kernels.cs_ema_tiled import cs_ema_tiled
    depth, width, d, k = 3, 16, 128, 40
    rng = np.random.RandomState(7)
    M = jnp.asarray(rng.randn(depth, width, d), jnp.float32)
    V = jnp.abs(jnp.asarray(rng.randn(depth, width, d), jnp.float32))
    b = jnp.asarray(rng.randint(0, width, (depth, k)), jnp.int32)
    s = jnp.asarray(rng.choice([-1.0, 1.0], (depth, k)), jnp.float32)
    g = jnp.asarray(rng.randn(k, d), jnp.float32)

    def run():
        if kernel == "adam":
            return cs_adam_tiled(M, V, b, s, b[::-1], g, lr=1e-2, b1=0.9,
                                 b2=0.999, eps=1e-8, bc1=0.19, bc2=0.002,
                                 n_valid=k - 5, interpret=True)
        return cs_ema_tiled(M, b, s, g, jnp.ones((k, 1), jnp.float32),
                            beta=0.9, scale=0.1, n_valid=k - 5,
                            interpret=True)

    whole = run()
    # 3 tables x 8 padded rows x 4 B x 16 rows: at most 16 rows per call
    monkeypatch.setattr(rg, "_SMEM_BUDGET", 3 * 8 * 4 * 16)
    assert rg.split_calls(k, rg.rows_per_call(depth, 3, 8), 8) == (16, 3)
    split = run()
    for a, c in zip(whole, split):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


@jax.jit
def _adam_tiled_calls(M, V, b, s, g, n_valid):
    """``cs_adam_tiled`` in interpret mode, ``n_valid`` traced as the
    sparse step passes it."""
    from repro.kernels.cs_adam_tiled import cs_adam_tiled
    return cs_adam_tiled(M, V, b, s, b[::-1], g, lr=1e-2, b1=0.9, b2=0.999,
                         eps=1e-8, bc1=0.19, bc2=0.002, n_valid=n_valid,
                         interpret=True)


# k = 48 rows in calls of 16: no row, one, a partial tile, one tile, a
# call boundary and one past it, a ragged last call, every row
@pytest.mark.parametrize("n_valid", [0, 1, 7, 8, 16, 17, 43, 48])
def test_tiled_adam_skips_rows_past_n_valid(n_valid, monkeypatch):
    """Rows past ``n_valid`` do no work, over several calls: with every one
    of them redrawn (other buckets, signs and gradients) M, V and the live
    update rows are bit-identical, every update row past ``n_valid`` reads
    0 (+0.0 past the live tiles), and with no live row M and V come back
    unchanged."""
    from repro.kernels import row_groups as rg
    depth, width, d, k, tile = 3, 16, 128, 48, 8
    rng = np.random.RandomState(11)
    M = jnp.asarray(rng.randn(depth, width, d), jnp.float32)
    V = jnp.abs(jnp.asarray(rng.randn(depth, width, d), jnp.float32))
    b = rng.randint(0, width, (depth, k))
    s = rng.choice([-1.0, 1.0], (depth, k))
    g = rng.randn(k, d)
    b2, s2, g2 = b.copy(), s.copy(), g.copy()
    b2[:, n_valid:] = rng.randint(0, width, (depth, k - n_valid))
    s2[:, n_valid:] *= -1
    g2[n_valid:] = 1e3 * rng.randn(k - n_valid, d)
    live = -(-n_valid // tile) * tile
    nv = jnp.int32(n_valid)
    monkeypatch.setattr(rg, "_SMEM_BUDGET", 3 * 8 * 4 * 16)
    assert rg.split_calls(k, rg.rows_per_call(depth, 3, tile), tile) \
        == (16, 3)

    def run(b, s, g):
        return _adam_tiled_calls(M, V, jnp.asarray(b, jnp.int32),
                                 jnp.asarray(s, jnp.float32),
                                 jnp.asarray(g, jnp.float32), nv)

    def bits(a):
        return np.asarray(a).view(np.uint32)

    got, alt = run(b, s, g), run(b2, s2, g2)
    np.testing.assert_array_equal(bits(got[0]), bits(alt[0]))
    np.testing.assert_array_equal(bits(got[1]), bits(alt[1]))
    np.testing.assert_array_equal(bits(got[2][:n_valid]),
                                  bits(alt[2][:n_valid]))
    for out in (got, alt):
        assert np.all(np.asarray(out[2][n_valid:]) == 0)
        assert np.all(bits(out[2][live:]) == 0)
    if n_valid == 0:
        np.testing.assert_array_equal(bits(got[0]), bits(M))
        np.testing.assert_array_equal(bits(got[1]), bits(V))
