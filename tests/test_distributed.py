"""Sharding rules, ZeRO-1 specs, elastic planning,
sketched data-parallel reduction (traffic accounting + error feedback).

Multi-replica semantics are simulated with ``vmap(axis_name=...)`` — the
collectives (psum / all_gather) behave identically to shard_map's, on one
device.  The real 8-device shard_map grid lives in
tests/test_distributed_dp.py (CI: the distributed-smoke job)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from repro.distributed import sharding as shd
from repro.distributed.elastic import (ElasticPlan, elastic_restore,
                                       plan_resize, recovery_loop)


def _mesh(shape=(2, 1), axes=("data", "model")):
    devs = np.array(jax.devices()[:1] * int(np.prod(shape))).reshape(shape)
    return Mesh(devs, axes)


# a fake 16x16 mesh purely for spec derivation (no computation placed):
# spec_for/dp_axes only read .axis_names and .devices.shape
def _fake_mesh(shape=(16, 16), axes=("data", "model")):
    M = type("FakeMesh", (), {})()
    M.axis_names = axes
    M.devices = type("D", (), {"shape": tuple(shape),
                               "size": int(np.prod(shape))})
    return M


class TestSpecRules:
    MESH = _fake_mesh()

    def test_vocab_tables_row_sharded(self):
        s = shd.spec_for("tok_embed/table", (92544, 6144), self.MESH)
        assert s == P("model")

    def test_attention_projections(self):
        assert shd.spec_for("layers/attn/wq", (48, 6144, 6144), self.MESH) \
            == P(None, None, "model")
        assert shd.spec_for("layers/attn/wo", (48, 6144, 6144), self.MESH) \
            == P(None, "model")

    def test_divisibility_fallback(self):
        # 14-head qwen2 wq output dim 896: divisible as a raw dim — but kv
        # proj of 2*64=128: 128 % 16 == 0 too; a truly indivisible dim:
        s = shd.spec_for("layers/attn/wk", (24, 896, 120), self.MESH)
        assert s == P()  # 120 % 16 != 0 -> replicated

    def test_norms_replicated(self):
        assert shd.spec_for("layers/ln1", (48, 6144), self.MESH) == P()
        assert shd.spec_for("final_norm", (6144,), self.MESH) == P()

    def test_moe_ep_vs_tp(self):
        ep = shd.spec_for("layers/ffn/w_gate", (24, 128, 5120, 8192),
                          self.MESH, expert_sharding="ep")
        assert ep == P(None, "model")
        tp = shd.spec_for("layers/ffn/w_gate", (24, 60, 2048, 1408),
                          self.MESH, expert_sharding="tp")
        assert tp == P(None, None, None, "model")

    def test_fsdp_adds_data_axis(self):
        s = shd.spec_for("layers/ffn/w_gate", (24, 128, 5120, 8192),
                         self.MESH, fsdp=True, expert_sharding="ep")
        assert s == P(None, "model", None, "data")

    def test_zero1_moment_sharding(self):
        base = P(None, "model")
        z = shd.zero1_spec(base, (48, 6144, 6144), self.MESH)
        assert z == P("data", "model")  # first unsharded divisible dim? 48%16!=0
        # 48 not divisible -> lands on dim... check actual behavior:
        # dim0=48 %16 !=0, dim1=6144 ok but taken? base P(None,'model') maps
        # dim0=None dim1='model'; third dim unsharded: 6144 % 16 == 0
        # so expected P(None, 'model', 'data')
        assert z in (P(None, "model", "data"), P("data", "model"))

    def test_sketch_spec(self):
        s = shd.sketch_spec(self.MESH, (3, 4096, 6144))
        assert s == P(None, "data", "model")
        s2 = shd.sketch_spec(self.MESH, (3, 100, 100))  # indivisible
        assert s2 == P()

    def test_dp_axes_divisibility(self):
        assert shd.dp_axes(self.MESH, 256) == ("data",)
        assert shd.dp_axes(self.MESH, 1) == ()
        m3 = _fake_mesh((2, 16, 16), ("pod", "data", "model"))
        assert shd.dp_axes(m3, 32) == ("pod", "data")
        assert shd.dp_axes(m3, 16) == ("data",)


class TestConstraint:
    def test_noop_outside_mesh(self):
        x = jnp.ones((4, 4))
        y = shd.constraint(x, P("data", None))
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_applies_inside_mesh(self):
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)

        @jax.jit
        def f(x):
            return shd.constraint(x, P("data", "model"))

        with shd.active_mesh(mesh):
            out = f(jnp.ones((4, 4)))
        assert out.shape == (4, 4)

    def test_drops_indivisible(self):
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)

        @jax.jit
        def f(x):
            return shd.constraint(x, P("data", "model"))

        with shd.active_mesh(mesh):
            out = f(jnp.ones((3, 5)))   # indivisible dims -> dropped axes
        assert out.shape == (3, 5)


class TestElastic:
    def test_plan_resize_keeps_tp(self):
        plan = plan_resize(240, model_axis=16, old_data_axis=16)
        assert plan.model_axis == 16
        assert plan.data_axis == 8        # largest pow2 <= 240/16
        assert plan.fold_sketch           # 2x fewer data shards -> fold

    def test_plan_resize_small_loss_no_fold(self):
        plan = plan_resize(256, model_axis=16, old_data_axis=16)
        assert plan.data_axis == 16 and not plan.fold_sketch

    def test_plan_resize_insufficient(self):
        with pytest.raises(ValueError):
            plan_resize(8, model_axis=16)

    def test_recovery_loop_restarts(self):
        state = {"restores": 0}

        def restore():
            state["restores"] += 1
            return state.get("ckpt", 0)

        def run_steps(start, total):
            for s in range(start, total):
                if s == 5 and state["restores"] == 1:
                    state["ckpt"] = 4
                    raise RuntimeError("chip failure")
            return total

        out = recovery_loop(run_steps, restore, total_steps=10)
        assert out.final_step == 10
        assert out.restarts == 1


class TestSketchedReduce:
    """Beyond-paper sketched DP reduction: psum(sketch(g)) == sketch(psum(g))."""

    def test_linearity_across_replicas(self):
        from repro.core import sketch as cs
        from repro.distributed import sketched_reduce as sr
        spec = cs.for_param((512, 16), compression=4.0, width_multiple=16,
                            seed=3)
        rng = np.random.RandomState(0)
        ids = jnp.asarray(rng.randint(0, 512, size=32), jnp.int32)
        g1 = jnp.asarray(rng.randn(32, 16), jnp.float32)
        g2 = jnp.asarray(rng.randn(32, 16), jnp.float32)
        # "two replicas" simulated by explicit sum
        summed = sr.local_sketch(spec, ids, g1 + g2)
        reduced = sr.local_sketch(spec, ids, g1) + sr.local_sketch(spec, ids, g2)
        np.testing.assert_allclose(np.asarray(summed), np.asarray(reduced),
                                   atol=1e-5)
        assert sr.traffic_ratio(spec, 512) > 2.0

    def test_psum_inside_shard_map(self):
        from repro.core import sketch as cs
        from repro.distributed import sketched_reduce as sr
        from jax.sharding import PartitionSpec as P
        mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
        spec = cs.for_param((128, 8), compression=4.0, width_multiple=8)
        ids = jnp.arange(16, dtype=jnp.int32)
        rows = jnp.ones((16, 8), jnp.float32)

        def f(ids, rows):
            return sr.reduce_gradient_sketch(spec, ids, rows, "data")

        out = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P(), P()), out_specs=P()))(ids, rows)
        want = sr.local_sketch(spec, ids, rows)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=1e-6)


class TestTrafficRatio:
    """Bytes-based accounting: dtype-aware, ids payload charged to dense."""

    def test_matches_explicit_byte_sizes(self):
        from repro.core import sketch as cs
        from repro.distributed import sketched_reduce as sr
        spec = cs.SketchSpec(depth=3, width=1024, dim=64)
        n = 50_000
        dense = n * 64 * 4 + n * 4            # f32 rows + int32 ids
        sketched = 3 * 1024 * 64 * 4          # spec.nbytes()
        assert sr.dense_reduce_bytes(n, 64) == dense
        assert sr.sketched_reduce_bytes(spec) == sketched
        assert sr.traffic_ratio(spec, n) == pytest.approx(dense / sketched)

    def test_dtype_aware(self):
        from repro.core import sketch as cs
        from repro.distributed import sketched_reduce as sr
        f32 = cs.SketchSpec(depth=3, width=1024, dim=64)
        bf16 = cs.SketchSpec(depth=3, width=1024, dim=64,
                             dtype=jnp.bfloat16)
        # a bf16 sketch moves half the bytes -> double the ratio
        assert sr.traffic_ratio(bf16, 50_000) == pytest.approx(
            2.0 * sr.traffic_ratio(f32, 50_000))
        # bf16 GRADIENT rows halve the dense side instead
        assert sr.traffic_ratio(f32, 50_000, grad_dtype=jnp.bfloat16,
                                with_ids=False) == pytest.approx(
            0.5 * sr.traffic_ratio(f32, 50_000, with_ids=False))

    def test_extra_specs_share_the_collective(self):
        from repro.core import sketch as cs
        from repro.distributed import sketched_reduce as sr
        m = cs.SketchSpec(depth=3, width=1024, dim=64)
        v = cs.SketchSpec(depth=3, width=512, dim=64, signed=False)
        lone = sr.traffic_ratio(m, 50_000)
        both = sr.traffic_ratio(m, 50_000, extra_specs=(v,))
        assert both < lone
        assert both == pytest.approx(
            sr.dense_reduce_bytes(50_000, 64) / (m.nbytes() + v.nbytes()))

    def test_paper_compressions_exceed_5x(self):
        # the acceptance regime: LM1B-style (n, d) tables at the paper's
        # 5x+ compression with a full-table (k == n) gradient batch
        from repro.core import sketch as cs
        from repro.distributed import sketched_reduce as sr
        for compression in (5.0, 10.0, 20.0):
            spec_m = cs.for_param((500_000, 64), compression=compression)
            spec_v = cs.for_param((500_000, 64), compression=compression,
                                  signed=False)
            ratio = sr.traffic_ratio(spec_m, 500_000,
                                     extra_specs=(spec_v,))
            assert ratio >= 5.0 * (compression / 10.0)


def _vmap_replicas(fn, *sharded):
    """Run ``fn`` per-replica over axis 'data' with collective semantics
    (vmap axis_name == shard_map collectives, single device)."""
    return jax.vmap(fn, axis_name="data")(*sharded)


class TestReduceMomentsFeedback:
    """The error-feedback hook: the reduced 2nd moment misses the
    cross-replica terms of (Σ_r g_r)²; feedback recovers them."""

    def _split(self, rng, n, d, R, k):
        ids = jnp.asarray(rng.randint(0, n, size=(R, k)), jnp.int32)
        rows = jnp.asarray(rng.randn(R, k, d), jnp.float32)
        return ids, rows

    def test_identity_sketch_feedback_is_exact(self):
        # identity sketches = exact tables: the bias and its correction
        # can be quantified exactly.  Per unique id i:
        #   no feedback:  Σ_r g_r[i]²        (underestimates)
        #   truth:        (Σ_r g_r[i])²
        #   feedback:     exact correction (g_sum query is exact)
        from repro.core import sketch as cs
        from repro.distributed import sketched_reduce as sr
        n, d, R, k = 32, 4, 4, 8
        spec_m = cs.for_param((n, d), compression=1.0, identity=True,
                              width_multiple=8)
        spec_v = cs.for_param((n, d), compression=1.0, identity=True,
                              width_multiple=8, signed=False)
        rng = np.random.RandomState(0)
        # one shared id across every replica (maximal cross terms);
        # aligned (non-negative) gradients: the −g² share clip never
        # binds, so the correction is EXACT
        ids = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32), (R, k))
        rows = jnp.asarray(np.abs(rng.randn(R, k, d)), jnp.float32)
        res0 = sr.init_feedback(spec_v)

        def f(ids_r, rows_r):
            return sr.reduce_moments(spec_m, spec_v, ids_r, rows_r,
                                     "data", residual=res0)

        G_m, G_v, res = _vmap_replicas(f, ids, rows)
        G_m, G_v, res = G_m[0], G_v[0], res[0]
        probe = jnp.arange(k, dtype=jnp.int32)
        got_v = np.asarray(cs.query(spec_v, G_v, probe))
        truth = np.asarray(jnp.square(jnp.sum(rows, axis=0)))
        np.testing.assert_allclose(got_v, truth, rtol=1e-4, atol=1e-5)
        # the residual fully drained (truth >= 0 per bucket, no clamping)
        np.testing.assert_allclose(np.asarray(res), 0.0, atol=1e-4)
        # and the exact linear part: G_m == sketch of the summed gradient
        want_m = sr.local_sketch(spec_m, probe, jnp.sum(rows, axis=0))
        np.testing.assert_allclose(np.asarray(G_m), np.asarray(want_m),
                                   rtol=1e-5, atol=1e-5)

    def test_clipped_feedback_never_undershoots_truth(self):
        # anti-aligned gradients: the share clip binds, making the
        # correction conservative — the estimate stays >= the true
        # (Σg)², never zeroing v below reality (the stability contract)
        from repro.core import sketch as cs
        from repro.distributed import sketched_reduce as sr
        n, d, R, k = 32, 4, 4, 8
        spec_m = cs.for_param((n, d), compression=1.0, identity=True,
                              width_multiple=8)
        spec_v = cs.for_param((n, d), compression=1.0, identity=True,
                              width_multiple=8, signed=False)
        rng = np.random.RandomState(2)
        ids = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32), (R, k))
        rows = jnp.asarray(rng.randn(R, k, d), jnp.float32)  # mixed signs
        res0 = sr.init_feedback(spec_v)

        def f(ids_r, rows_r):
            return sr.reduce_moments(spec_m, spec_v, ids_r, rows_r,
                                     "data", residual=res0)

        _, G_v, _ = _vmap_replicas(f, ids, rows)
        probe = jnp.arange(k, dtype=jnp.int32)
        got = np.asarray(cs.query(spec_v, G_v[0], probe))
        truth = np.asarray(jnp.square(jnp.sum(rows, axis=0)))
        assert (got >= truth - 1e-4).all()
        assert (got >= -1e-6).all()

    def test_no_feedback_underestimates_by_cross_term(self):
        from repro.core import sketch as cs
        from repro.distributed import sketched_reduce as sr
        n, d, R, k = 32, 4, 4, 8
        spec_m = cs.for_param((n, d), compression=1.0, identity=True,
                              width_multiple=8)
        spec_v = cs.for_param((n, d), compression=1.0, identity=True,
                              width_multiple=8, signed=False)
        rng = np.random.RandomState(1)
        ids = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32), (R, k))
        rows = jnp.asarray(rng.randn(R, k, d), jnp.float32)

        def f(ids_r, rows_r):
            return sr.reduce_moments(spec_m, spec_v, ids_r, rows_r, "data")

        _, G_v, res = _vmap_replicas(f, ids, rows)
        assert res is None
        probe = jnp.arange(k, dtype=jnp.int32)
        got = np.asarray(cs.query(spec_v, G_v[0], probe))
        sum_sq = np.asarray(jnp.sum(jnp.square(rows), axis=0))
        truth = np.asarray(jnp.square(jnp.sum(rows, axis=0)))
        # the modeled bias: estimate == Σg² exactly, i.e. off from the
        # single-replica ground truth by exactly the cross term
        np.testing.assert_allclose(got, sum_sq, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(truth - got,
                                   truth - sum_sq, rtol=1e-4, atol=1e-5)

    def test_feedback_reduces_error_with_real_sketches(self):
        # noisy sketches: collision noise hits both estimators equally;
        # feedback removes the systematic cross-term bias, so its mean
        # error vs the single-replica ground truth must be lower
        from repro.core import sketch as cs
        from repro.distributed import sketched_reduce as sr
        n, d, R, k = 512, 8, 4, 48
        spec_m = cs.for_param((n, d), compression=2.0, width_multiple=64,
                              seed=7)
        spec_v = cs.for_param((n, d), compression=2.0, width_multiple=64,
                              seed=8, signed=False)
        errs = {True: [], False: []}
        for trial in range(4):
            rng = np.random.RandomState(100 + trial)
            # every replica touches the same k distinct ids: maximal
            # cross-replica overlap, correlated gradients (worst case)
            probe = jnp.asarray(
                rng.choice(n, size=k, replace=False), jnp.int32)
            ids = jnp.broadcast_to(probe, (R, k))
            common = rng.randn(1, k, d)
            rows = jnp.asarray(rng.randn(R, k, d) * 0.3 + common,
                               jnp.float32)
            truth = np.asarray(jnp.square(jnp.sum(rows, axis=0)))
            for fb in (True, False):
                res0 = sr.init_feedback(spec_v) if fb else None

                def f(ids_r, rows_r):
                    return sr.reduce_moments(spec_m, spec_v, ids_r, rows_r,
                                             "data", residual=res0)

                _, G_v, _ = _vmap_replicas(f, ids, rows)
                est = np.asarray(cs.query(spec_v, G_v[0], probe))
                errs[fb].append(float(np.mean(np.abs(est - truth))))
        assert np.mean(errs[True]) < np.mean(errs[False])


class TestOptStateSharding:
    """The ZeRO-1 rules against REAL init'd optimizer state trees — the
    chain/AuxStore layouts of PR 3, not the pre-refactor {'step','m','v'}
    monolith layout.  No silent replication fallbacks for sketch leaves."""

    MESH = _fake_mesh()

    def _params(self):
        return {"tok_embed": {"table": jnp.zeros((8192, 64))},
                "final_norm": jnp.zeros((64,))}

    def _spec_map(self, state, params, **kw):
        specs = shd.opt_specs_for_state(
            jax.eval_shape(lambda: state), params, self.MESH, **kw)
        flat, _ = jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, P))
        out = {}
        for kp, leaf in flat:
            out["/".join(shd._kp_str(kp))] = leaf
        return out

    def _sketch_opt(self):
        from repro.core import optimizers as O
        from repro.core.stores import CountMinStore, CountSketchStore
        return dict(
            m_store=CountSketchStore(compression=5.0),
            v_store=CountMinStore(compression=5.0),
            where=lambda p, s: len(s) == 2 and s[0] >= 1024)

    def test_legacy_layout_sketch_leaves_sharded(self):
        from repro.core import optimizers as O
        from repro.core.transforms import scale_by_adam
        params = self._params()
        opt = O.countsketch_adam(
            1e-3, policy=lambda p, s: len(s) == 2 and s[0] >= 1024)
        state = opt.init(params)
        sm = self._spec_map(state, params)
        assert sm["m/tok_embed/table"] == P(None, "data", "model")
        assert sm["v/tok_embed/table"] == P(None, "data", "model")
        assert sm["step"] == P()
        # dense norm moment: replicated param spec (1-D, 64 % 16 == 0
        # -> ZeRO-1 picks up 'data'... 64 >= 16 and divisible)
        assert "data" in tuple(sm["m/final_norm"]) or \
            sm["m/final_norm"] == P()

    def test_chain_layout_resolves_through_tuple_indices(self):
        from repro.core.transforms import (chain, clip_by_global_norm,
                                           scale_by_adam, scale_by_lr)
        params = self._params()
        opt = chain(clip_by_global_norm(1.0),
                    scale_by_adam(**self._sketch_opt()),
                    scale_by_lr(1e-3))
        state = opt.init(params)
        sm = self._spec_map(state, params)
        assert sm["1/m/tok_embed/table"] == P(None, "data", "model")
        assert sm["1/v/tok_embed/table"] == P(None, "data", "model")
        assert sm["2/step"] == P()

    def test_rank1_factors_replicate(self):
        from repro.core.transforms import scale_by_adam
        from repro.core.stores import Rank1Store
        params = self._params()
        opt = scale_by_adam(v_store=Rank1Store(),
                            where=lambda p, s: len(s) == 2)
        state = opt.init(params)
        sm = self._spec_map(state, params)
        r_keys = [k for k in sm if "tok_embed/table" in k and k.startswith("v/")]
        assert len(r_keys) == 2          # the (r, c) factor pair
        for k in r_keys:
            assert sm[k] == P()

    def test_bare_sparse_rows_state(self):
        from repro.core import optimizers as O
        from repro.core.optimizers import SketchHParams
        opt = O.sparse_rows_adam_dp(
            1e-3, shape=(8192, 64), hparams=SketchHParams(),
            error_feedback=True)
        state = opt.init()
        table = jnp.zeros((8192, 64))
        sm = self._spec_map(state, table)
        assert sm["m"] == P(None, "data", "model")
        assert sm["v"] == P(None, "data", "model")
        assert sm["residual"] == P(None, "data", "model")
        assert sm["step"] == P()

    def test_store_tree_classification_is_exact(self):
        from repro.core import optimizers as O
        from repro.core.stores import (CountMinStore, CountSketchStore,
                                       DenseStore, StoreTree)
        params = self._params()
        tree = StoreTree(rules=(
            ("tok_embed/table",
             CountSketchStore(compression=5.0).bind(
                 "tok_embed/table", (8192, 64), jnp.float32),
             CountMinStore(compression=5.0).bind(
                 "tok_embed/table", (8192, 64), jnp.float32)),),
            default_m=DenseStore(), default_v=DenseStore())
        opt = O.adam_from_stores(1e-3, tree)
        state = opt.init(params)
        sm = self._spec_map(state, params, store_tree=tree)
        assert sm["m/tok_embed/table"] == P(None, "data", "model")
        assert sm["v/tok_embed/table"] == P(None, "data", "model")

    def test_strict_raises_on_unclassifiable_sketch(self):
        params = self._params()
        bogus = {"m": {"tok_embed": {"table": jnp.zeros((3, 512, 100))}},
                 "step": jnp.zeros((), jnp.int32)}
        with pytest.raises(ValueError, match="refusing to silently"):
            shd.opt_specs_for_state(bogus, params, self.MESH)
        # non-strict: the old silent fallback, explicitly requested
        specs = shd.opt_specs_for_state(bogus, params, self.MESH,
                                        strict=False)
        assert specs["m"]["tok_embed"]["table"] == P()

    def test_train_step_shardings_cover_every_leaf(self):
        # the end-to-end surface: TrainStep.shardings on the real init'd
        # state must yield a NamedSharding for every array leaf, with
        # sketch leaves NOT silently replicated
        from repro import configs
        from repro.train.steps import make_train_step
        cfg = configs.get("qwen2_0_5b").reduced()
        ts = make_train_step(cfg, optimizer="cs_adam")
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        pshard, oshard, bshard, mshard = ts.shardings(mesh, {})
        os_ = ts.opt_shape()
        flat_o, _ = jax.tree_util.tree_flatten_with_path(
            os_, is_leaf=lambda x: x is None)
        flat_s, _ = jax.tree_util.tree_flatten_with_path(
            oshard, is_leaf=lambda x: x is None)
        # sharding tree mirrors the state tree leaf-for-leaf
        assert len(flat_o) == len(flat_s)
        n_sketch = 0
        for (kp, leaf), (_, sh) in zip(flat_o, flat_s):
            if leaf is None:
                continue
            assert sh is not None, f"no sharding for {kp}"
            if hasattr(leaf, "ndim") and leaf.ndim == 3 \
                    and leaf.shape[0] <= 8:
                n_sketch += 1
                assert tuple(sh.spec), \
                    f"sketch leaf {kp} silently replicated"
        assert n_sketch > 0     # cs_adam really sketched something


class TestElasticRestoreFold:
    """ElasticPlan.fold_sketch → checkpoint.fold_sketches, with the exact
    StoreTree predicate from the manifest."""

    def _setup(self, tmp_path):
        from repro.checkpoint import store
        from repro.core.stores import (CountMinStore, CountSketchStore,
                                       DenseStore, StoreTree)
        rng = np.random.RandomState(0)
        tree = StoreTree(rules=(
            ("tok_embed/table",
             CountSketchStore(compression=4.0, width_multiple=16).bind(
                 "tok_embed/table", (1024, 8), jnp.float32),
             CountMinStore(compression=4.0, width_multiple=16).bind(
                 "tok_embed/table", (1024, 8), jnp.float32)),),
            default_m=DenseStore(), default_v=DenseStore())
        m_store, v_store = tree.resolve("tok_embed/table", (1024, 8),
                                        jnp.float32)
        state = {
            "params": {"tok_embed": {"table": jnp.asarray(
                rng.randn(1024, 8), jnp.float32)}},
            "opt_state": {
                "step": jnp.asarray(7, jnp.int32),
                "m": {"tok_embed": {"table": jnp.asarray(
                    rng.randn(*m_store.spec.shape), jnp.float32)}},
                "v": {"tok_embed": {"table": jnp.asarray(
                    rng.rand(*v_store.spec.shape), jnp.float32)}},
            },
        }
        store.save(tmp_path, 7, state,
                   extra={"store_tree": tree.to_json()})
        return store, tree, state

    def test_fold_restore(self, tmp_path):
        store, tree, state = self._setup(tmp_path)
        plan = ElasticPlan(data_axis=8, model_axis=16, pods=1,
                           fold_sketch=True)
        step, restored, folded = elastic_restore(tmp_path, state, plan)
        assert step == 7 and folded
        m0 = np.asarray(state["opt_state"]["m"]["tok_embed"]["table"])
        mf = np.asarray(restored["opt_state"]["m"]["tok_embed"]["table"])
        w = m0.shape[1]
        assert mf.shape[1] == w // 2
        np.testing.assert_allclose(mf, m0[:, : w // 2] + m0[:, w // 2:],
                                   rtol=1e-6)
        # params and dense leaves untouched
        np.testing.assert_array_equal(
            np.asarray(restored["params"]["tok_embed"]["table"]),
            np.asarray(state["params"]["tok_embed"]["table"]))

    def test_no_fold_when_plan_says_no(self, tmp_path):
        store, tree, state = self._setup(tmp_path)
        plan = ElasticPlan(data_axis=16, model_axis=16, pods=1,
                           fold_sketch=False)
        _, restored, folded = elastic_restore(tmp_path, state, plan)
        assert not folded
        assert restored["opt_state"]["m"]["tok_embed"]["table"].shape == \
            state["opt_state"]["m"]["tok_embed"]["table"].shape

    def test_explicit_store_tree_wins_over_manifest(self, tmp_path):
        from repro.core.stores import DenseStore, StoreTree
        store, tree, state = self._setup(tmp_path)
        plan = ElasticPlan(data_axis=8, model_axis=16, pods=1,
                           fold_sketch=True)
        # an all-dense tree: the predicate matches nothing -> no fold
        dense_tree = StoreTree(rules=(), default_m=DenseStore(),
                               default_v=DenseStore())
        _, restored, folded = elastic_restore(tmp_path, state, plan,
                                              store_tree=dense_tree)
        assert folded   # the plan asked; predicate just matched nothing
        assert restored["opt_state"]["m"]["tok_embed"]["table"].shape == \
            state["opt_state"]["m"]["tok_embed"]["table"].shape
