"""Model-parallel sketches (DESIGN.md §17): slab primitives, sharded
parity, per-device planning, restore guards, and the obs gauges.

The slab primitives, planner, spec-classification, JSON, and report
tests run on a single device (the slab ops are pure functions of the
shard index).  The parity grid needs 8 devices — run under
XLA_FLAGS=--xla_force_host_platform_device_count=8 (CI's
``sharded-smoke`` job does); it skips otherwise.  The launcher restore
tests force their own 8-device subprocess, so they run everywhere.

Bit-exactness protocol (same as tests/test_distributed_dp.py): dyadic
hyperparameters (β₁ = β₂ = 0.5) and integer gradients make every
add/multiply in both data paths exact, so any grouping of the same real
sums is bit-equal.  Count-sketch linearity plus the slab decomposition
(every (depth-row, id) cell lives on exactly one shard) make the
sharded and replicated steps the same real numbers.
"""
import json
import os
import subprocess
import sys

import jax
from jax.sharding import AxisType
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sketch as cs
from repro.core.optimizers import SketchHParams
from repro.core.stores import CountMinStore, CountSketchStore, StoreTree
from repro.distributed import sharding as shd
from repro.plan.allocator import InfeasibleBudgetError, min_budget_bytes
from repro.plan.cli import plan_for_tables
from repro.plan.plan import MODE_SKETCH, Plan

N_DEV = 8
multidevice = pytest.mark.skipif(
    jax.device_count() < N_DEV,
    reason=f"needs {N_DEV} devices: run under XLA_FLAGS="
           f"--xla_force_host_platform_device_count={N_DEV} "
           f"(CI sharded-smoke job)")

N, D, B = 512, 16, 128          # table rows, dim, global batch
PATH = "sparse_embedding"


def _spec(layout, *, signed=True, shards=4, width=64, identity=False):
    return cs.SketchSpec(depth=3, width=width, dim=D, signed=signed,
                         seed=7, shards=shards, layout=layout,
                         identity=identity)


def _batch(seed, n=N, b=B, d=D):
    rng = np.random.RandomState(seed)
    ids = jnp.asarray(rng.randint(0, n, size=b), jnp.int32)
    rows = jnp.asarray(rng.randint(-3, 4, size=(b, d)), jnp.float32)
    return ids, rows


# ---------------------------------------------------------------------------
# Slab primitives: exact decomposition of update/query, both layouts
# ---------------------------------------------------------------------------

class TestSlabPrimitives:
    @pytest.mark.parametrize("layout", ["width", "hash"])
    @pytest.mark.parametrize("signed", [True, False])
    def test_update_slabs_concat_to_full_update(self, layout, signed):
        spec = _spec(layout, signed=signed)
        ids, rows = _batch(0)
        full = cs.update(spec, cs.init(spec), ids, rows)
        slabs = [cs.update_slab(spec, cs.init_slab(spec), ids, rows, s)
                 for s in range(spec.shards)]
        np.testing.assert_array_equal(np.concatenate(slabs, axis=1),
                                      np.asarray(full))

    @pytest.mark.parametrize("layout", ["width", "hash"])
    @pytest.mark.parametrize("signed", [True, False])
    def test_gather_slabs_sum_to_full_query(self, layout, signed):
        spec = _spec(layout, signed=signed)
        ids, rows = _batch(1)
        S = cs.update(spec, cs.init(spec), ids, rows)
        qids = ids[:32]
        parts = sum(cs.gather_slab(spec, cs.slab_of(spec, S, s), qids, s)
                    for s in range(spec.shards))
        est = cs.finish_query(spec, parts, qids)
        np.testing.assert_array_equal(np.asarray(est),
                                      np.asarray(cs.query(spec, S, qids)))

    def test_hash_layout_keeps_all_depth_rows_on_one_shard(self):
        # locality: an id's every depth row must land in its OWNER's
        # slab — a single-id update touches exactly one shard
        spec = _spec("hash")
        one = jnp.ones((1, D), jnp.float32)
        for i in [0, 1, 17, 255, 511]:
            ids = jnp.asarray([i], jnp.int32)
            touched = [s for s in range(spec.shards)
                       if float(jnp.sum(jnp.abs(cs.update_slab(
                           spec, cs.init_slab(spec), ids, one, s)))) > 0]
            assert len(touched) == 1, (i, touched)

    def test_width_layout_state_is_byte_identical_to_unsharded(self):
        # 'width' sharding is placement-only: same seed, same hashing,
        # same full tensor as the shards=1 spec
        ids, rows = _batch(2)
        sharded = _spec("width")
        plain = cs.SketchSpec(depth=3, width=64, dim=D, seed=7)
        a = cs.update(sharded, cs.init(sharded), ids, rows)
        b = cs.update(plain, cs.init(plain), ids, rows)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_kernel_registry_slab_ops_resolve(self):
        # the flat API coerces None/'auto'/slab-less backends to 'xla'
        from repro import kernels
        spec = _spec("hash", signed=True)
        ids, rows = _batch(3)
        base = cs.update_slab(spec, cs.init_slab(spec), ids, rows, 1)
        for backend in (None, "auto", "xla", "tiled"):
            got = kernels.update_slab(spec, cs.init_slab(spec), ids, rows,
                                      1, backend=backend)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


# ---------------------------------------------------------------------------
# Parity grid: sharded step vs replicated step, 8 forced devices
# ---------------------------------------------------------------------------

def _steps(layout, *, dp=False, track_m=True, feedback=False):
    """(init_fn, jitted sharded step + opt, reference step + opt).

    The reference must match the sharded run's DP split count (the DP
    2nd-moment per-replica squares depend on it): shard-only pairs with
    the single-device step, dp×shard (2, 4) pairs with a dp=2 run.  The
    hash layout re-derives buckets through the two-level owner hash, so
    its reference runs REPLICATED but with the same sharded-stamped
    stores (count-sketch state is identical; only placement differs)."""
    from repro.train.steps import make_sparse_embedding_step, \
        sparse_embedding_stores
    hp = SketchHParams(compression=2.0, width_multiple=64)
    kw = dict(lr=1e-2, b1=0.5, b2=0.5, hparams=hp, track_first_moment=track_m)
    if dp:
        shards = 4
        mesh = jax.make_mesh((N_DEV // shards, shards), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        ref_mesh = jax.make_mesh((N_DEV // shards,), ("data",),
                                 axis_types=(AxisType.Auto,))
    else:
        shards = N_DEV
        mesh = jax.make_mesh((N_DEV,), ("model",), axis_types=(AxisType.Auto,))
        # the sharded step applies the same dir_clip trust clamp as the
        # dp path, so the bit-parity reference is the dp step at dp=1,
        # not the clamp-less single-device step
        ref_mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    init_fn, sh_step, sh_opt = make_sparse_embedding_step(
        N, D, dp_axis="data" if dp else None, mesh=mesh,
        sketch_shards=shards, shard_layout=layout,
        error_feedback=feedback, **kw)
    m_st, v_st = sparse_embedding_stores(N, D, hparams=hp,
                                         track_first_moment=track_m,
                                         sketch_shards=shards,
                                         shard_layout=layout)
    tree = StoreTree(rules=((PATH, m_st, v_st),))
    _, ref_step, ref_opt = make_sparse_embedding_step(
        N, D, stores=tree, dp_axis="data", mesh=ref_mesh,
        error_feedback=feedback, **kw)
    return init_fn, (jax.jit(sh_step), sh_opt), (jax.jit(ref_step), ref_opt)


def _run_pair(init_fn, sharded, ref, steps=3):
    (sh_step, sh_opt), (ref_step, ref_opt) = sharded, ref
    table = init_fn(jax.random.PRNGKey(0))
    t_sh = t_ref = table
    s_sh, s_ref = sh_opt.init(), ref_opt.init()
    for seed in range(steps):
        ids, rows = _batch(seed)
        t_sh, s_sh = sh_step(t_sh, s_sh, ids, rows)
        t_ref, s_ref = ref_step(t_ref, s_ref, ids, rows)
    return (t_sh, s_sh), (t_ref, s_ref)


def _assert_state_equal(s_sh, s_ref):
    for k in ("m", "v", "residual"):
        a, b = s_sh.get(k), s_ref.get(k)
        assert (a is None) == (b is None), k
        if a is not None:
            assert np.array_equal(np.asarray(a), np.asarray(b)), k


class TestShardedParityGrid:
    @multidevice
    @pytest.mark.parametrize("layout", ["width", "hash"])
    @pytest.mark.parametrize("track_m", [True, False])
    def test_shard_only_bit_identical_to_replicated(self, layout, track_m):
        init_fn, sharded, ref = _steps(layout, dp=False, track_m=track_m)
        (t_sh, s_sh), (t_ref, s_ref) = _run_pair(init_fn, sharded, ref)
        assert np.array_equal(np.asarray(t_sh), np.asarray(t_ref))
        _assert_state_equal(s_sh, s_ref)

    @multidevice
    @pytest.mark.parametrize("layout", ["width", "hash"])
    @pytest.mark.parametrize("feedback", [False, True])
    def test_dp_x_shard_bit_identical_to_dp_reference(self, layout,
                                                      feedback):
        init_fn, sharded, ref = _steps(layout, dp=True, feedback=feedback)
        (t_sh, s_sh), (t_ref, s_ref) = _run_pair(init_fn, sharded, ref)
        assert np.array_equal(np.asarray(t_sh), np.asarray(t_ref))
        _assert_state_equal(s_sh, s_ref)

    @multidevice
    def test_sharded_state_is_placed_on_the_shard_axis(self):
        init_fn, (sh_step, sh_opt), _ = _steps("width", dp=True)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        state = jax.device_put(
            sh_opt.init(),
            shd.named(mesh, shd.sketch_state_specs(
                jax.eval_shape(sh_opt.init))))
        v = state["v"]
        assert v.sharding.spec == jax.sharding.PartitionSpec(None, "model")


# ---------------------------------------------------------------------------
# opt_specs_for_state: sharded-sketch classification (satellite)
# ---------------------------------------------------------------------------

def _sharded_tree(shards=1, layout="width", width=64):
    m = CountSketchStore(width=width, depth=3, width_multiple=64, seed=7)
    v = CountMinStore(width=width, depth=3, width_multiple=64, seed=7)
    if shards > 1:
        m = m.with_sharding(shards, layout)
        v = v.with_sharding(shards, layout)
    return StoreTree(rules=(("emb/table", m, v),))


class TestOptSpecsShardedClassification:
    def _mesh2d(self):
        return jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)

    def _state(self, chain_prefix="0/", residual=False):
        st = {"step": jnp.zeros(()),
              "m": jnp.zeros((3, 64, D)), "v": jnp.zeros((3, 64, D))}
        if residual:
            st["residual"] = jnp.zeros((3, 64, D))
        # chain-indexed layout: {"0": {...}} flattens to 0/m/... paths
        return ({chain_prefix.rstrip("/"): {
            k: ({"emb": {"table": x}} if k != "step" else x)
            for k, x in st.items()}} if chain_prefix else st)

    def test_chain_indexed_sharded_state_lands_on_shard_axis(self):
        mesh = self._mesh2d()
        params = {"emb": {"table": jnp.zeros((N, D))}}
        specs = shd.opt_specs_for_state(
            self._state(), params, mesh,
            store_tree=_sharded_tree(shards=4, layout="hash"))
        P = jax.sharding.PartitionSpec
        assert specs["0"]["m"]["emb"]["table"] == P(None, "model")
        assert specs["0"]["v"]["emb"]["table"] == P(None, "model")

    def test_residual_leaf_follows_the_v_sketch(self):
        mesh = self._mesh2d()
        params = {"emb": {"table": jnp.zeros((N, D))}}
        specs = shd.opt_specs_for_state(
            self._state(residual=True), params, mesh,
            store_tree=_sharded_tree(shards=4))
        assert specs["0"]["residual"]["emb"]["table"] == \
            jax.sharding.PartitionSpec(None, "model")

    def test_strict_raises_on_sharded_store_without_shard_axis(self):
        # a mesh with NO 'model' axis cannot place 4-shard sketch state;
        # strict must refuse to silently replicate it
        mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
        params = {"emb": {"table": jnp.zeros((N, D))}}
        with pytest.raises(ValueError, match="refusing to silently"):
            shd.opt_specs_for_state(
                self._state(), params, mesh,
                store_tree=_sharded_tree(shards=4), strict=True)

    def test_unsharded_tree_keeps_the_classic_placement(self):
        mesh = self._mesh2d()
        params = {"emb": {"table": jnp.zeros((N, D))}}
        specs = shd.opt_specs_for_state(
            self._state(), params, mesh, store_tree=_sharded_tree())
        assert specs["0"]["m"]["emb"]["table"] != \
            jax.sharding.PartitionSpec(None, "model")


# ---------------------------------------------------------------------------
# Planner: per-device budgets + the llama4 regression + JSON round-trip
# ---------------------------------------------------------------------------

LLAMA4_VOCAB = {"tok_embed/table": (202048, 5120),
                "lm_head/table": (202048, 5120)}


class TestPerShardPlanning:
    def test_llama4_vocab_requires_sharding(self):
        # the motivating config: aux_budget_bytes below the unsharded
        # CS-MV floor of the vocab pair (DESIGN.md §17)
        from repro.configs.llama4_maverick_400b_a17b import CONFIG
        budget = CONFIG.aux_budget_bytes
        ps = {p: jax.ShapeDtypeStruct(s, jnp.float32)
              for p, s in LLAMA4_VOCAB.items()}
        assert min_budget_bytes(ps) > budget
        with pytest.raises(InfeasibleBudgetError):
            plan_for_tables(LLAMA4_VOCAB, budget, optimizer="cs_adam")
        plan = plan_for_tables(LLAMA4_VOCAB, budget, optimizer="cs_adam",
                               shards=8)
        assert plan.predicted_aux_bytes_per_device <= budget
        assert plan.predicted_aux_bytes > budget
        for leaf in plan.leaves:
            assert leaf.mode == MODE_SKETCH

    def test_sharded_plan_stamps_stores_and_specs(self):
        plan = plan_for_tables({"tok_embed/table": (100000, 64)},
                               256 * 2**10, optimizer="cs_adam", shards=8,
                               shard_layout="hash")
        m_st, v_st = plan.store_tree().resolve("tok_embed/table",
                                               (100000, 64), jnp.float32)
        assert v_st.shards == 8 and v_st.shard_layout == "hash"
        assert m_st.spec.shards == 8 and m_st.spec.layout == "hash"
        assert v_st.spec.width % 8 == 0

    def test_plan_json_round_trips_sharding(self):
        plan = plan_for_tables({"tok_embed/table": (100000, 64)},
                               256 * 2**10, optimizer="cs_adam", shards=8)
        d = plan.to_json()
        assert d["sketch_shards"] == 8
        back = Plan.from_json(json.loads(json.dumps(d)))
        assert back.sketch_shards == 8 and back.shard_layout == "width"
        assert back.predicted_aux_bytes_per_device == \
            plan.predicted_aux_bytes_per_device

    def test_unsharded_plan_json_stays_back_compatible(self):
        plan = plan_for_tables({"tok_embed/table": (100000, 64)}, "0.25x",
                               optimizer="cs_rmsprop")
        d = plan.to_json()
        assert "sketch_shards" not in d and "shard_layout" not in d
        back = Plan.from_json(d)
        assert back.sketch_shards == 1
        assert back.predicted_aux_bytes_per_device == \
            back.predicted_aux_bytes

    def test_with_sharding_validates_width_divisibility(self):
        plan = plan_for_tables({"tok_embed/table": (100000, 64)}, "0.25x",
                               optimizer="cs_rmsprop")
        width = next(l.width for l in plan.leaves if l.mode == MODE_SKETCH)
        bad = width * 3          # no plan width is a multiple of this
        with pytest.raises(ValueError):
            plan.with_sharding(bad)

    def test_shard_table_renders_per_device_bytes(self):
        plan = plan_for_tables({"tok_embed/table": (100000, 64)},
                               256 * 2**10, optimizer="cs_adam", shards=8)
        text = plan.shard_table()
        assert "PER-DEVICE" in text
        assert f"{plan.predicted_aux_bytes_per_device:,}" in text


# ---------------------------------------------------------------------------
# Store gauges + report warning (satellite)
# ---------------------------------------------------------------------------

class TestShardObservability:
    def test_sharded_store_stats_emit_per_shard_occupancy(self):
        v = CountMinStore(width=64, depth=3, width_multiple=64) \
            .with_sharding(4, "hash").bind("emb/table", (N, D), jnp.float32)
        ids, rows = _batch(5)
        state = v.accumulate(v.init(), jnp.abs(rows), rows=ids)
        stats = v.stats(state)
        assert {"shard_occ_min", "shard_occ_max"} <= set(stats)
        assert 0.0 < float(stats["shard_occ_min"]) \
            <= float(stats["shard_occ_max"]) <= 1.0

    def test_unsharded_store_stats_have_no_shard_gauges(self):
        v = CountMinStore(width=64, depth=3, width_multiple=64) \
            .bind("emb/table", (N, D), jnp.float32)
        assert "shard_occ_min" not in v.stats(v.init())

    def _table_record(self, lo, hi):
        return [{"kind": "table", "step": 10, "table": "emb/table",
                 "v_occupancy": 0.5, "v_shard_occ_min": lo,
                 "v_shard_occ_max": hi}]

    def test_report_warns_on_shard_imbalance(self):
        from repro.obs.report import analyze
        digest = analyze(self._table_record(0.1, 0.9))
        assert any("shard-imbalance" in w for w in digest["warnings"])

    def test_report_silent_on_balanced_shards(self):
        from repro.obs.report import analyze
        digest = analyze(self._table_record(0.5, 0.6))
        assert not [w for w in digest["warnings"] if "shard-imbalance" in w]


# ---------------------------------------------------------------------------
# Elastic restore across shard counts (launcher subprocess, 8 forced dev)
# ---------------------------------------------------------------------------

def _launch(tmp_path, extra, steps):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src")
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.train",
         "--workload", "sparse_embedding", "--sparse-rows", "4096",
         "--sparse-dim", "16", "--batch", "8", "--seq", "32",
         "--steps", str(steps), "--ckpt-dir", str(tmp_path),
         "--ckpt-every", "6", "--lr", "1e-2"] + extra,
        capture_output=True, text=True, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), env=env)


class TestElasticRestoreAcrossShardCounts:
    def test_width_layout_replaces_across_shard_counts(self, tmp_path):
        r1 = _launch(tmp_path, ["--sketch-shards", "4"], steps=12)
        assert r1.returncode == 0, r1.stderr[-2000:]
        r2 = _launch(tmp_path, ["--sketch-shards", "8"], steps=18)
        assert r2.returncode == 0, r2.stderr[-2000:]
        assert "re-placed: 4 -> 8 shards" in r2.stdout

    def test_hash_layout_refuses_changed_shard_count(self, tmp_path):
        r1 = _launch(tmp_path, ["--sketch-shards", "4",
                                "--shard-layout", "hash"], steps=12)
        assert r1.returncode == 0, r1.stderr[-2000:]
        r2 = _launch(tmp_path, ["--sketch-shards", "8",
                                "--shard-layout", "hash"], steps=18)
        assert r2.returncode != 0
        assert "bakes the shard count" in r2.stderr
