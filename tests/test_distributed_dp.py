"""Multi-device data-parallel parity grid (DESIGN.md §13).

Run under XLA_FLAGS=--xla_force_host_platform_device_count=8 (the CI
``distributed-smoke`` job does): the ``shard_map`` DP sparse-embedding
step must produce a 1st-moment sketch BIT-IDENTICAL to the single-device
step on the concatenated batch, and a 2nd moment within the modeled
cross-replica bias bound.

Bit-exactness protocol: count-sketch linearity makes the DP and the
single-device 1st-moment updates the same REAL number; to make them the
same FLOAT we pin the parity grid to dyadic hyperparameters (β₁ = β₂ =
0.5) and integer-valued gradients, for which every add/multiply in both
data paths is exact — any grouping of exact dyadic sums is bit-equal.
The float-noise-tolerant variant is covered by the vmap tests in
tests/test_distributed.py.

With fewer than 8 devices everything here skips except the launcher
end-to-end test, which forces its own 8-device subprocess.
"""
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
from repro.distributed import sharding as shd
from repro.kernels import ops

N_DEV = 8
multidevice = pytest.mark.skipif(
    jax.device_count() < N_DEV,
    reason=f"needs {N_DEV} devices: run under XLA_FLAGS="
           f"--xla_force_host_platform_device_count={N_DEV} "
           f"(CI distributed-smoke job)")

N, D, B = 512, 16, 128          # table rows, dim, global batch


def _mesh():
    return jax.make_mesh((N_DEV, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def _batch(seed):
    rng = np.random.RandomState(seed)
    ids = jnp.asarray(rng.randint(0, N, size=B), jnp.int32)
    rows = jnp.asarray(rng.randint(-3, 4, size=(B, D)), jnp.float32)
    return ids, rows


def _steps(track_m, feedback, *, compression=2.0, identity=False):
    from repro.train.steps import make_sparse_embedding_step
    hp = SketchHParams(compression=compression, width_multiple=64,
                       identity=identity)
    kw = dict(lr=1e-2, b1=0.5, b2=0.5, hparams=hp,
              track_first_moment=track_m)
    init_fn, dp_step, dp_opt = make_sparse_embedding_step(
        N, D, dp_axis="data", mesh=_mesh(), error_feedback=feedback, **kw)
    _, ref_step, ref_opt = make_sparse_embedding_step(N, D, **kw)
    return init_fn, (jax.jit(dp_step), dp_opt), (ref_step, ref_opt)


class TestDpParityGrid:
    @multidevice
    @pytest.mark.parametrize("track_m", [True, False])
    @pytest.mark.parametrize("feedback", [False, True])
    def test_first_moment_bit_identical(self, track_m, feedback):
        init_fn, (dp_step, dp_opt), (ref_step, ref_opt) = _steps(
            track_m, feedback)
        table = init_fn(jax.random.PRNGKey(0))
        t_dp = t_ref = table
        s_dp, s_ref = dp_opt.init(), ref_opt.init()
        for seed in range(3):
            ids, rows = _batch(seed)
            t_dp, s_dp = dp_step(t_dp, s_dp, ids, rows)
            t_ref, s_ref = ref_step(t_ref, s_ref, ids, rows)
            if track_m:
                assert np.array_equal(np.asarray(s_dp["m"]),
                                      np.asarray(s_ref["m"])), \
                    f"M diverged at step {seed + 1}"
            else:
                assert s_dp["m"] is None
            assert int(s_dp["step"]) == int(s_ref["step"])

    @multidevice
    def test_second_moment_within_modeled_bias(self):
        # one step from zero state: the ONLY difference between the DP
        # and single-device V updates is the missing cross-replica term
        # (1-β₂)·sketch(cross), cross_i = (Σ_r g_r[i])² − Σ_r g_r[i]².
        # The modeled bound is that term's exact sketch magnitude.
        init_fn, (dp_step, dp_opt), (ref_step, ref_opt) = _steps(
            True, False)
        table = init_fn(jax.random.PRNGKey(0))
        s_dp, s_ref = dp_opt.init(), ref_opt.init()
        ids, rows = _batch(0)
        _, s_dp = dp_step(table, s_dp, ids, rows)
        _, s_ref = ref_step(table, s_ref, ids, rows)
        spec_v = dp_opt_spec_v = None
        # reconstruct spec_v exactly as the step derived it
        hp = SketchHParams(compression=2.0, width_multiple=64)
        spec_v = hp.spec("sparse_embedding", (N, D), signed=False)
        # exact per-unique-id cross term on the host
        shard_ids = np.asarray(ids).reshape(N_DEV, -1)
        shard_rows = np.asarray(rows).reshape(N_DEV, -1, D)
        g_sum = np.zeros((N, D)); g_sq = np.zeros((N, D))
        for r in range(N_DEV):
            gr = np.zeros((N, D))
            np.add.at(gr, shard_ids[r], shard_rows[r])
            g_sum += gr
            g_sq += gr * gr
        cross = g_sum * g_sum - g_sq
        touched = np.where(np.abs(cross).sum(1) > 0)[0].astype(np.int32)
        bound_sketch = cs.update(spec_v, cs.init(spec_v),
                                 jnp.asarray(touched),
                                 jnp.asarray(np.abs(cross[touched]),
                                             jnp.float32))
        bound = (1.0 - 0.5) * np.asarray(bound_sketch) + 1e-4
        diff = np.abs(np.asarray(s_dp["v"]) - np.asarray(s_ref["v"]))
        assert (diff <= bound).all(), \
            f"V bias {diff.max()} exceeds modeled bound {bound.max()}"

    @multidevice
    def test_error_feedback_exact_with_identity_sketches(self):
        # identity sketches + aligned (non-negative) gradients make the
        # cross-term estimate exact and the −g² clip inactive, so the
        # feedback-corrected DP second moment equals the single-device
        # one (up to float association)
        init_fn, (dp_step, dp_opt), (ref_step, ref_opt) = _steps(
            True, True, identity=True)
        table = init_fn(jax.random.PRNGKey(0))
        s_dp, s_ref = dp_opt.init(), ref_opt.init()
        ids, rows = _batch(0)
        rows = jnp.abs(rows)
        _, s_dp = dp_step(table, s_dp, ids, rows)
        _, s_ref = ref_step(table, s_ref, ids, rows)
        np.testing.assert_allclose(np.asarray(s_dp["v"]),
                                   np.asarray(s_ref["v"]),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(s_dp["residual"]), 0.0,
                                   atol=1e-5)

    @multidevice
    def test_state_shardings_resolve_on_the_dp_mesh(self):
        from repro.core import optimizers as O
        opt = O.sparse_rows_adam_dp(
            1e-2, shape=(N, D),
            hparams=SketchHParams(compression=2.0, width_multiple=64),
            error_feedback=True)
        state = opt.init()
        mesh = _mesh()
        specs = shd.opt_specs_for_state(
            jax.eval_shape(lambda: state), jnp.zeros((N, D)), mesh)
        # width (multiple of 64) shards over the 8-way data axis
        assert tuple(specs["m"]) [:2] == (None, "data")
        assert tuple(specs["v"])[:2] == (None, "data")
        assert tuple(specs["residual"])[:2] == (None, "data")


class TestDpServeAdapt:
    @multidevice
    def test_online_adapt_dp_matches_single_device_update_rule(self):
        # β₁=0 serve adaptation: the numerator is the reduced gradient
        # sketch's estimate; with identity sketches + error feedback both
        # the estimate and the 2nd moment (cross-replica duplicates
        # included) are exact, so DP == single-device
        from repro.serve.steps import make_online_adapt_step
        hp = SketchHParams(compression=1.0, width_multiple=64,
                           identity=True)
        init_dp, adapt_dp = make_online_adapt_step(
            N, D, lr=1e-2, b2=0.5, hparams=hp, dp_axis="data",
            mesh=_mesh(), error_feedback=True)
        init_1, adapt_1 = make_online_adapt_step(
            N, D, lr=1e-2, b2=0.5, hparams=hp)
        rng = np.random.RandomState(3)
        table = jnp.asarray(rng.randn(N, D), jnp.float32)
        ids, rows = _batch(3)
        rows = jnp.abs(rows)     # aligned grads: the share clip is exact
        s_dp, s_1 = init_dp(), init_1()
        t_dp, t_1 = table, table
        for _ in range(2):
            t_dp, s_dp = jax.jit(adapt_dp)(t_dp, s_dp, ids, rows)
            t_1, s_1 = adapt_1(t_1, s_1, ids, rows)
        np.testing.assert_allclose(np.asarray(s_dp["v"]),
                                   np.asarray(s_1["v"]),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(t_dp), np.asarray(t_1),
                                   rtol=1e-4, atol=1e-5)


class TestDpLmStep:
    @multidevice
    def test_lm_dp_matches_gspmd_loss(self):
        from repro import configs
        from repro.train.steps import make_train_step
        cfg = configs.get("qwen2_0_5b").reduced()
        mesh = _mesh()
        ts_dp = make_train_step(cfg, optimizer="cs_adam", dp_axis="data")
        ts_ref = make_train_step(cfg, optimizer="cs_adam")
        rng = np.random.RandomState(0)
        batch = {
            "tokens": jnp.asarray(
                rng.randint(0, cfg.vocab, size=(N_DEV * 2, 32)), jnp.int32),
            "labels": jnp.asarray(
                rng.randint(0, cfg.vocab, size=(N_DEV * 2, 32)), jnp.int32),
        }
        with shd.active_mesh(mesh):
            params = ts_dp.init_fn(jax.random.PRNGKey(0))
            s_dp = ts_dp.optimizer.init(params)
            s_ref = ts_ref.optimizer.init(params)
            p_dp, s_dp, m_dp = jax.jit(ts_dp.step_fn)(params, s_dp, batch)
            p_ref, s_ref, m_ref = jax.jit(ts_ref.step_fn)(params, s_ref,
                                                          batch)
        # per-replica mean loss pmean'd == global mean loss
        np.testing.assert_allclose(float(m_dp["loss"]),
                                   float(m_ref["loss"]), rtol=1e-4)
        np.testing.assert_allclose(float(m_dp["grad_norm"]),
                                   float(m_ref["grad_norm"]), rtol=1e-3)
        # params actually moved, identically up to collective association
        moved = jax.tree_util.tree_reduce(
            lambda a, b: a + float(jnp.sum(jnp.abs(b))),
            jax.tree_util.tree_map(lambda a, b: a - b, p_dp, params), 0.0)
        assert moved > 0.0


class TestLauncherEndToEnd:
    def test_sparse_embedding_dp_trains_through_launcher(self, tmp_path):
        """launch/train.py --workload sparse_embedding --dp on a forced
        8-device host platform: exits 0 only if the loss decreased."""
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.getcwd(), "src"),
             env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.train",
             "--workload", "sparse_embedding", "--dp", "--error-feedback",
             "--steps", "20", "--batch", "16", "--seq", "16",
             "--sparse-rows", "4096", "--sparse-dim", "32",
             "--lr", "0.05",
             "--ckpt-dir", str(tmp_path / "ckpt")],
            capture_output=True, text=True, env=env, timeout=600)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "workload=sparse_embedding" in out.stdout
        assert "dp=True" in out.stdout
