"""Fused store execution (DESIGN.md §14): ``update_read`` parity grid,
registry dispatch, single-kernel lowering, and the cleaning hook.

Agreement tiers, from exact to statistical (mirroring the PR-1 backend
suite):

  1. store-level ``update_read`` on 'ref' and 'xla' is BIT-identical to
     the composed decay→accumulate→read fallback — same gathers, the
     shared ``sketch.ema_delta`` increment form, same scatter (the
     hypothesis grid below, stores × backends × dtypes × EMA forms);
  2. 'tiled'/'interpret' (the Pallas kernel) is bit-identical on
     collision-free row sets (identity hashing) and matches the composed
     path within estimator-noise tolerance under real hashing — the
     difference is cross-tile streaming semantics, exactly as for the
     PR-1 tiled Adam kernel;
  3. at the transform level, the fused whole-table path equals the
     UNCHUNKED composed path bit-for-bit; vs the default chunked-scan
     fallback the residual is XLA fusion (fma) reassociation inside
     ``lax.scan`` — ≤ a few ulp, asserted tightly (DESIGN.md §14).

Plus: ``scale_by_adam`` lowers to ONE fused kernel per moment on the
Pallas backends (jaxpr inspection — the acceptance bar), the §4 cleaning
hook fires on the fused dense path, and the backend knob round-trips
through StoreTree/Plan JSON.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - exercised only without hypothesis
    class _Strategies:
        @staticmethod
        def integers(lo, hi):
            return lambda rng: int(rng.randint(lo, hi + 1))

        @staticmethod
        def floats(lo, hi):
            return lambda rng: float(rng.uniform(lo, hi))

        @staticmethod
        def sampled_from(seq):
            seq = list(seq)
            return lambda rng: seq[rng.randint(len(seq))]

    st = _Strategies()

    def settings(max_examples=10, **_kw):
        def deco(fn):
            fn._max_examples = min(max_examples, 10)
            return fn
        return deco

    def given(**strats):
        def deco(fn):
            def wrapper(*args):
                rng = np.random.RandomState(0)
                for _ in range(getattr(wrapper, "_max_examples", 10)):
                    fn(*args, **{name: draw(rng)
                                 for name, draw in strats.items()})
            wrapper.__name__ = fn.__name__
            wrapper.__doc__ = fn.__doc__
            return wrapper
        return deco

from repro.core import optimizers as O
from repro.core import transforms as T
from repro.core.cleaning import CleaningSchedule
from repro.core.partition import SketchPolicy
from repro.core.stores import (CountMinStore, CountSketchStore, DenseStore,
                               Rank1Store, StoreTree, store_from_json,
                               store_to_json)
from repro.kernels import registry
from repro.plan import Plan


def _tree_equal(a, b, atol=0.0):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        if atol:
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=atol)
        else:
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# store-level parity grid
# ---------------------------------------------------------------------------

# (beta, scale or None-for-default): the three EMA forms the transforms
# use — Adam (scale = 1-β), momentum (scale = 1), Adagrad (β = 1)
EMA_FORMS = {"adam": (0.9, None), "momentum": (0.9, 1.0),
             "adagrad": (1.0, 1.0), "adam_b2": (0.999, None)}


def _bound(cls, *, n=384, d=8, dtype="float32", identity=False, seed=0,
           backend=None):
    return cls(compression=4.0, width_multiple=16, dtype=dtype, seed=seed,
               identity=identity, backend=backend).bind(
                   "tok_embed/table", (n, d), jnp.float32)


class TestUpdateReadParityGrid:
    """Satellite: fused implementations vs the composed fallback."""

    @settings(max_examples=16, deadline=None)
    @given(cls=st.sampled_from([CountSketchStore, CountMinStore]),
           backend=st.sampled_from(["ref", "xla"]),
           form=st.sampled_from(sorted(EMA_FORMS)),
           dtype=st.sampled_from(["float32", "bfloat16"]),
           masked=st.sampled_from([True, False]),
           rows=st.sampled_from([None, 64]),
           seed=st.integers(0, 3))
    def test_ref_xla_bit_identical_to_composed(self, cls, backend, form,
                                               dtype, masked, rows, seed):
        """'ref' and 'xla' run the same gathers / ``ema_delta`` form /
        scatter as the composed fallback — bit-identical, every dtype,
        masked or not, whole-table or row subset."""
        beta, scale = EMA_FORMS[form]
        st0 = _bound(cls, dtype=dtype, seed=seed)
        rng = np.random.RandomState(seed)
        S = jnp.asarray(rng.randn(*st0.spec.shape), st0.spec.dtype)
        k = 64 if rows is not None else 384
        ids = jnp.asarray(rng.choice(384, k, replace=False), jnp.int32) \
            if rows is not None else None
        x = jnp.asarray(rng.randn(k, 8), jnp.float32)
        mask = jnp.asarray(rng.rand(k, 1) > 0.3, jnp.float32) \
            if masked else None
        want = st0.update_read(S, x, beta, scale=scale, rows=ids, mask=mask)
        got = dataclasses.replace(st0, backend=backend).update_read(
            S, x, beta, scale=scale, rows=ids, mask=mask)
        _tree_equal(want, got)

    @settings(max_examples=8, deadline=None)
    @given(cls=st.sampled_from([CountSketchStore, CountMinStore]),
           form=st.sampled_from(sorted(EMA_FORMS)),
           masked=st.sampled_from([True, False]),
           seed=st.integers(0, 3))
    def test_interpret_exact_collision_free(self, cls, form, masked, seed):
        """The Pallas kernel (interpret mode off-TPU) on an identity-
        hashed (collision-free) sketch: exact — the dedup-equivalence
        argument of DESIGN.md §10 applied to the single-store op."""
        beta, scale = EMA_FORMS[form]
        st0 = _bound(cls, n=64, identity=True, seed=seed)
        rng = np.random.RandomState(seed)
        S = jnp.asarray(rng.randn(*st0.spec.shape), jnp.float32)
        x = jnp.asarray(rng.randn(64, 8), jnp.float32)
        mask = jnp.asarray(rng.rand(64, 1) > 0.3, jnp.float32) \
            if masked else None
        want = st0.update_read(S, x, beta, scale=scale, mask=mask)
        got = dataclasses.replace(st0, backend="interpret").update_read(
            S, x, beta, scale=scale, mask=mask)
        _tree_equal(want, got, atol=1e-6)

    def test_interpret_tolerance_under_collisions(self):
        """Real hashing, width ≪ n: the tiled kernel's cross-tile
        streaming may differ from the composed batch semantics only on
        bucket-colliding rows, by estimator noise — bounded here with
        fixed seeds (same protocol as the PR-1 tiled-Adam envelope)."""
        worst_s = worst_e = 0.0
        for seed in range(3):
            st0 = _bound(CountMinStore, n=384, seed=seed)
            rng = np.random.RandomState(seed)
            S = jnp.abs(jnp.asarray(rng.randn(*st0.spec.shape), jnp.float32))
            x = jnp.asarray(rng.randn(384, 8) ** 2, jnp.float32)
            Sw, ew = st0.update_read(S, x, 0.999)
            Sg, eg = dataclasses.replace(st0, backend="interpret") \
                .update_read(S, x, 0.999)
            worst_s = max(worst_s, float(
                np.linalg.norm(np.asarray(Sw) - np.asarray(Sg))
                / np.linalg.norm(np.asarray(Sw))))
            worst_e = max(worst_e, float(np.max(np.abs(
                np.asarray(ew) - np.asarray(eg)))))
        # empirically calibrated envelopes (observed: state 2e-5, est 2e-3)
        assert worst_s < 1e-3, worst_s
        assert worst_e < 0.5, worst_e

    def test_dense_and_rank1_defaults_match_primitives(self):
        """The base composed default on closed-form stores: decay →
        accumulate → read, bit-for-bit."""
        ds = DenseStore().bind("w", (16, 4), jnp.float32)
        state = jnp.ones((16, 4))
        g = jnp.full((16, 4), 0.5)
        s1, e1 = ds.update_read(state, g, 0.9)
        want = ds.accumulate(ds.decay(state, 0.9), g, scale=0.1)
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(e1), np.asarray(want))
        r1 = Rank1Store().bind("t", (16, 4), jnp.float32)
        st0 = r1.init()
        s2, e2 = r1.update_read(st0, g * g, 0.999, scale=1e-3)
        want2 = r1.accumulate(r1.decay(st0, 0.999), g * g, scale=1e-3)
        _tree_equal(s2, want2)
        np.testing.assert_array_equal(np.asarray(e2),
                                      np.asarray(r1.read(want2)))

    def test_strict_mode_requeries(self):
        """strict=True (the 3-pass paper semantics) re-reads after the
        write — est equals query(state'), not est_old + Δ."""
        st0 = _bound(CountMinStore, n=64, identity=True)
        S = st0.init()
        x = jnp.ones((64, 8))
        S1, e1 = st0.update_read(S, x, 1.0, scale=1.0, strict=True)
        np.testing.assert_array_equal(
            np.asarray(e1), np.asarray(st0.read(S1)))


# ---------------------------------------------------------------------------
# registry dispatch
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_rows(self):
        assert ("pair", "adam_rows") in registry.ops()
        assert ("sketch", "update_read") in registry.ops()
        assert ("countmin", "update_read") in registry.ops()

    def test_update_read_backends(self):
        for kind in ("sketch", "countmin"):
            assert registry.backends(kind, "update_read") == \
                ("ref", "xla", "tiled", "interpret")

    def test_pair_row_keeps_pr1_contents(self):
        assert registry.backends("pair", "adam_rows") == \
            ("ref", "xla", "tiled", "interpret")

    def test_resolve(self):
        want = "tiled" if jax.default_backend() == "tpu" else "xla"
        assert registry.resolve("sketch", "update_read", None) == want
        assert registry.resolve("sketch", "update_read", "auto") == want
        with pytest.raises(KeyError):
            registry.resolve("sketch", "update_read", "stream")
        with pytest.raises(KeyError):
            registry.backends("sketch", "nope")


# ---------------------------------------------------------------------------
# transform-level: single-kernel lowering + fused/composed agreement
# ---------------------------------------------------------------------------

POL = SketchPolicy(min_rows=256)


def _tree(backend=None, identity=False, cleaning=None):
    return StoreTree.select(
        m=CountSketchStore(compression=4.0, width_multiple=16,
                           backend=backend, identity=identity),
        v=CountMinStore(compression=4.0, width_multiple=16,
                        backend=backend, identity=identity,
                        cleaning=cleaning),
        where=POL)


def _setup(seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    params = {"tok_embed": {"table": jax.random.normal(k1, (512, 8))},
              "w": jax.random.normal(k2, (16, 16))}
    grads = jax.tree_util.tree_map(
        lambda p: jax.random.normal(k2, p.shape) * 0.1, params)
    # zero-grad rows exercise the lazy mask on the fused path too
    grads["tok_embed"]["table"] = \
        grads["tok_embed"]["table"].at[100:140].set(0.0)
    return params, grads


def _count_prim(jaxpr, name, acc=0):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            acc += 1
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):
                acc = _count_prim(v.jaxpr, name, acc)
            elif isinstance(v, (list, tuple)):
                for vv in v:
                    if hasattr(vv, "jaxpr"):
                        acc = _count_prim(vv.jaxpr, name, acc)
    return acc


class TestFusedLowering:
    def test_one_fused_kernel_per_moment_no_scan(self):
        """The acceptance bar: on the Pallas backend a sketched dense
        leaf lowers to exactly ONE fused kernel per moment — two
        pallas_call for (m, v), zero lax.scan — while the composed
        fallback is scan-shaped with no kernels."""
        params, grads = _setup()
        opt = T.scale_by_adam(stores=_tree("interpret"))
        state = opt.init(params)
        fused = jax.make_jaxpr(lambda g, s: opt.update(g, s))(grads, state)
        assert _count_prim(fused.jaxpr, "pallas_call") == 2
        assert _count_prim(fused.jaxpr, "scan") == 0
        composed = T.scale_by_adam(stores=_tree(None))
        cj = jax.make_jaxpr(
            lambda g, s: composed.update(g, s))(grads, state)
        assert _count_prim(cj.jaxpr, "pallas_call") == 0
        assert _count_prim(cj.jaxpr, "scan") >= 1

    def test_rmsprop_single_kernel(self):
        """β₁=0 layout: one kernel total (no m moment)."""
        params, grads = _setup()
        opt = T.scale_by_rmsprop(stores=_tree("interpret"))
        state = opt.init(params)
        j = jax.make_jaxpr(lambda g, s: opt.update(g, s))(grads, state)
        assert _count_prim(j.jaxpr, "pallas_call") == 1

    def test_fused_equals_unchunked_composed_bitwise(self):
        """fused 'ref'/'xla' vs the UNCHUNKED composed path: identical op
        sequence → identical bits (states and updates, multi-step)."""
        params, grads = _setup()
        ref_opt = T.scale_by_adam(stores=_tree(None), dense_chunk=0)
        for backend in ("ref", "xla"):
            opt = T.scale_by_adam(stores=_tree(backend))
            s0, s1 = ref_opt.init(params), opt.init(params)
            for _ in range(3):
                u0, s0 = ref_opt.update(grads, s0, params)
                u1, s1 = opt.update(grads, s1, params)
            _tree_equal((u0, s0), (u1, s1))

    def test_fused_vs_default_chunked_within_ulps(self):
        """vs the DEFAULT chunked-scan fallback the residual is XLA fma
        reassociation inside lax.scan — a few ulp on O(1) values,
        asserted tightly (documented in DESIGN.md §14)."""
        params, grads = _setup()
        c_opt = T.scale_by_adam(stores=_tree(None))
        f_opt = T.scale_by_adam(stores=_tree("xla"))
        sc, sf = c_opt.init(params), f_opt.init(params)
        for _ in range(3):
            uc, sc = c_opt.update(grads, sc, params)
            uf, sf = f_opt.update(grads, sf, params)
        _tree_equal((uc, sc), (uf, sf), atol=1e-5)

    def test_momentum_adagrad_fused_paths(self):
        params, grads = _setup()
        for make in (lambda be: T.scale_by_momentum(
                        stores=StoreTree.select(
                            m=CountSketchStore(compression=4.0,
                                               width_multiple=16,
                                               backend=be),
                            v=None, where=POL, default_v=None)),
                     lambda be: T.scale_by_adagrad(
                        stores=StoreTree.select(
                            v=CountMinStore(compression=4.0,
                                            width_multiple=16, backend=be),
                            m=None, where=POL, default_m=None))):
            ref_opt = make(None)
            opt = make("xla")
            s0, s1 = ref_opt.init(params), opt.init(params)
            for _ in range(2):
                u0, s0 = ref_opt.update(grads, s0, params)
                u1, s1 = opt.update(grads, s1, params)
            _tree_equal((u0, s0), (u1, s1), atol=1e-5)

    def test_strict_paper_ignores_backend(self):
        """strict_paper forces the composed 3-pass semantics even when a
        backend is pinned (no fused kernels in the jaxpr)."""
        params, grads = _setup()
        opt = T.scale_by_adam(stores=_tree("interpret"), strict_paper=True)
        state = opt.init(params)
        j = jax.make_jaxpr(lambda g, s: opt.update(g, s))(grads, state)
        assert _count_prim(j.jaxpr, "pallas_call") == 0


# ---------------------------------------------------------------------------
# cleaning hook on the fused path (satellite)
# ---------------------------------------------------------------------------

class TestCleaningOnFusedPath:
    @pytest.mark.parametrize("backend", [None, "xla", "interpret"])
    def test_cleaning_schedule_mutates_v_sketch(self, backend):
        """Regression: a CleaningSchedule must actually decay the
        2nd-moment sketch during ``scale_by_adam`` steps — composed AND
        fused paths.  With α=0.5 every 2 steps, the cleaned run's sketch
        mass must be strictly below the uncleaned run's after step 2."""
        params, grads = _setup()
        clean = CleaningSchedule(alpha=0.5, every=2)
        opt_c = T.scale_by_adam(stores=_tree(backend, cleaning=clean))
        opt_n = T.scale_by_adam(stores=_tree(backend))
        sc, sn = opt_c.init(params), opt_n.init(params)
        for _ in range(2):
            _, sc = opt_c.update(grads, sc, params)
            _, sn = opt_n.update(grads, sn, params)
        v_c = np.abs(np.asarray(sc["v"]["tok_embed"]["table"])).sum()
        v_n = np.abs(np.asarray(sn["v"]["tok_embed"]["table"])).sum()
        assert v_c < 0.9 * v_n, (v_c, v_n)
        # step 1 and 3 are off-schedule: states agree between the runs
        # up to the decayed carry (sanity: cleaning fired exactly once)
        _, sc = opt_c.update(grads, sc, params)
        v_c2 = np.abs(np.asarray(sc["v"]["tok_embed"]["table"])).sum()
        assert v_c2 > v_c  # accumulation resumed, no extra decay


# ---------------------------------------------------------------------------
# backend as a first-class store/plan dimension
# ---------------------------------------------------------------------------

class TestBackendThreading:
    def test_store_json_roundtrip(self):
        # spec-pinned form (what plans/manifests serialize)
        spec = _bound(CountMinStore).spec
        st0 = CountMinStore(spec=spec, shape=(384, 8), backend="tiled")
        assert store_from_json(store_to_json(st0)) == st0
        # factory form round-trips the backend too
        st1 = CountMinStore(compression=4.0, width_multiple=16,
                            backend="xla")
        assert store_from_json(store_to_json(st1)) == st1
        # absent key (old manifests) -> None backend
        d = store_to_json(CountMinStore(spec=spec, shape=(384, 8)))
        assert "backend" not in d
        assert store_from_json(d).backend is None

    def test_store_tree_with_backend(self):
        tree = _tree(None)
        fused = tree.with_backend("xla")
        m, v = fused.resolve("tok_embed/table", (512, 8), jnp.float32)
        assert m.backend == v.backend == "xla"
        # dense leaves untouched
        m, v = fused.resolve("w", (16, 16), jnp.float32)
        assert (m.kind, v.kind) == ("dense", "dense")
        # spec/seed layout untouched: states interchangeable
        m0, v0 = tree.resolve("tok_embed/table", (512, 8), jnp.float32)
        assert (m.spec if hasattr(m, "spec") else None) is None or True
        m1, v1 = fused.resolve("tok_embed/table", (512, 8), jnp.float32)
        assert m0.spec == m1.spec and v0.spec == v1.spec

    def test_plan_roundtrip_and_normalization(self):
        from repro.plan import plan_for_params
        params = {"tok_embed": {"table": jnp.zeros((2048, 16))},
                  "w": jnp.zeros((32, 32))}
        plan = plan_for_params(params, 80_000, width_multiple=16,
                               min_rows=512)
        fused = plan.with_backend("tiled")
        assert fused != plan
        assert fused.with_backend(None) == plan
        # serialization carries the backend; old manifests (no key) load
        rt = Plan.from_json(fused.to_json())
        assert rt == fused
        d = plan.to_json()
        d.pop("backend")
        assert Plan.from_json(d) == plan
        # the emitted StoreTree pins the backend on every sketched leaf
        tree = fused.store_tree()
        for path, m, v in tree.rules:
            if v.kind == "countmin":
                assert v.backend == "tiled"
                if m is not None and m.kind == "sketch":
                    assert m.backend == "tiled"

    def test_plan_make_optimizer_backend_runs_fused(self):
        from repro.plan import plan_for_params
        params = {"tok_embed": {"table": jnp.zeros((2048, 16))},
                  "w": jnp.zeros((32, 32))}
        plan = plan_for_params(params, 80_000, width_multiple=16,
                               min_rows=512)
        opt = plan.make_optimizer(1e-3, backend="interpret")
        grads = jax.tree_util.tree_map(jnp.ones_like, params)
        state = opt.init(params)
        j = jax.make_jaxpr(lambda g, s: opt.update(g, s))(grads, state)
        assert _count_prim(j.jaxpr, "pallas_call") >= 1
