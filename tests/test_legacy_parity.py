"""Legacy-API parity (ISSUE 3 satellite): the refactored wrappers vs the
frozen pre-refactor monoliths in tests/legacy_reference.py.

Property grid (hypothesis when installed, a seeded shim otherwise) over
policies × moment modes × hparams: the wrapped ``countsketch_{momentum,
adagrad,adam}`` must produce bit-identical states AND updates to the
reference over a 3-step trajectory.  Plus: checkpoints written by the
old API restore under the new one and continue bit-identically, and a
planner ``Plan`` round-trips through a checkpoint manifest as a
``StoreTree`` (no PolicyFn/overrides in the serialized form) that
rebuilds the exact same optimizer.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - exercised only without hypothesis
    class _Strategies:
        """Tiny stand-in: each strategy describes one seeded draw."""

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
            # no functools.wraps: pytest must see only *args (bound self),
            # not the property's params (it would mistake them for fixtures)
            def wrapper(*args):
                rng = np.random.RandomState(0)
                # @settings sits OUTSIDE @given, so it annotates `wrapper`
                for _ in range(getattr(wrapper, "_max_examples", 10)):
                    fn(*args, **{name: draw(rng)
                                 for name, draw in strats.items()})
            wrapper.__name__ = fn.__name__
            wrapper.__doc__ = fn.__doc__
            return wrapper
        return deco

import legacy_reference as L

from repro.checkpoint import store as ckpt
from repro.core import optimizers as O
from repro.core.cleaning import CleaningSchedule
from repro.core.partition import (SketchPolicy, everything_policy,
                                  nothing_policy)


def _setup(seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = {"tok_embed": {"table": jax.random.normal(k1, (512, 8))},
              "lm_head": {"table": jax.random.normal(k3, (384, 8))},
              "w": jax.random.normal(k2, (16, 16))}
    grads = jax.tree_util.tree_map(
        lambda p: jax.random.normal(k2, p.shape) * 0.1, params)
    # a zero-grad row block exercises the lazy (row-active) masking
    grads["tok_embed"]["table"] = \
        grads["tok_embed"]["table"].at[100:140].set(0.0)
    return params, grads


def _tree_equal(a, b):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _run_pair(make, steps=3):
    """Build the (legacy-reference, refactored) pair and step both."""
    params, grads = _setup()
    ref, new = make(L), make(O)
    sr, sn = ref.init(params), new.init(params)
    p_r = p_n = params
    for _ in range(steps):
        ur, sr = ref.update(grads, sr, p_r)
        un, sn = new.update(grads, sn, p_n)
        _tree_equal(ur, un)        # updates bit-identical
        _tree_equal(sr, sn)        # moment states bit-identical
        p_r, p_n = O.apply_updates(p_r, ur), O.apply_updates(p_n, un)
    _tree_equal(p_r, p_n)


POLICIES = {
    "nothing": nothing_policy,
    "tables": SketchPolicy(min_rows=256),
    "everything": everything_policy,
}
# (track_first_moment, sketch_first_moment, rank1 on lm_head)
MOMENT_MODES = {
    "mv": (True, True, False),
    "cs_v": (True, False, False),
    "b1_zero": (False, False, False),
    "rank1_mix": (True, True, True),
}
CLEANINGS = {"none": None, "aggressive": CleaningSchedule(alpha=0.5, every=2)}


class TestAdamParityGrid:
    @settings(max_examples=12, deadline=None)
    @given(policy=st.sampled_from(sorted(POLICIES)),
           mode=st.sampled_from(sorted(MOMENT_MODES)),
           compression=st.sampled_from([2.0, 5.0]),
           depth=st.sampled_from([1, 3]),
           dense_chunk=st.sampled_from([0, 128]),
           strict=st.sampled_from([True, False]),
           cleaning=st.sampled_from(sorted(CLEANINGS)),
           override=st.sampled_from([False, True]))
    def test_countsketch_adam_bit_identical(self, policy, mode, compression,
                                            depth, dense_chunk, strict,
                                            cleaning, override):
        track, sketch_first, rank1 = MOMENT_MODES[mode]
        overrides = ((("tok_embed/table", (2, 32)),) if override else ())

        def make(mod):
            hp = mod.SketchHParams(
                compression=compression, depth=depth, width_multiple=16,
                dense_chunk=dense_chunk, strict_paper=strict,
                overrides=overrides)
            return mod.countsketch_adam(
                1e-2, policy=POLICIES[policy], hparams=hp,
                rank1_policy=(lambda p, s: "lm_head" in p) if rank1
                else nothing_policy,
                cleaning=CLEANINGS[cleaning],
                track_first_moment=track, sketch_first_moment=sketch_first)

        _run_pair(make)


class TestMomentumAdagradParityGrid:
    @settings(max_examples=8, deadline=None)
    @given(policy=st.sampled_from(sorted(POLICIES)),
           compression=st.sampled_from([2.0, 5.0]),
           dense_chunk=st.sampled_from([0, 128]),
           strict=st.sampled_from([True, False]))
    def test_countsketch_momentum_bit_identical(self, policy, compression,
                                                dense_chunk, strict):
        def make(mod):
            hp = mod.SketchHParams(compression=compression,
                                   width_multiple=16,
                                   dense_chunk=dense_chunk,
                                   strict_paper=strict)
            return mod.countsketch_momentum(0.1, policy=POLICIES[policy],
                                            hparams=hp)
        _run_pair(make)

    @settings(max_examples=8, deadline=None)
    @given(policy=st.sampled_from(sorted(POLICIES)),
           compression=st.sampled_from([2.0, 5.0]),
           dense_chunk=st.sampled_from([0, 128]),
           strict=st.sampled_from([True, False]),
           cleaning=st.sampled_from(sorted(CLEANINGS)))
    def test_countsketch_adagrad_bit_identical(self, policy, compression,
                                               dense_chunk, strict, cleaning):
        def make(mod):
            hp = mod.SketchHParams(compression=compression,
                                   width_multiple=16,
                                   dense_chunk=dense_chunk,
                                   strict_paper=strict)
            return mod.countsketch_adagrad(0.1, policy=POLICIES[policy],
                                           hparams=hp,
                                           cleaning=CLEANINGS[cleaning])
        _run_pair(make)


class TestOldCheckpointsRestore:
    def test_old_api_checkpoint_restores_under_new_api(self, tmp_path):
        """A checkpoint written from the pre-refactor optimizer's state
        restores into the refactored wrapper (same tree paths) and the
        run continues bit-identically to an uninterrupted reference."""
        params, grads = _setup()
        hp_kw = dict(compression=4.0, width_multiple=16)
        ref = O_ref = L.countsketch_adam(
            1e-2, policy=POLICIES["tables"],
            hparams=L.SketchHParams(**hp_kw))
        new = O.countsketch_adam(1e-2, policy=POLICIES["tables"],
                                 hparams=O.SketchHParams(**hp_kw))
        # run the OLD api 2 steps, checkpoint
        s_ref = ref.init(params)
        p_ref = params
        for _ in range(2):
            u, s_ref = ref.update(grads, s_ref, p_ref)
            p_ref = O.apply_updates(p_ref, u)
        ckpt.save(tmp_path, 2, {"params": p_ref, "opt_state": s_ref})
        # restore into the NEW api's state template
        like = {"params": jax.eval_shape(lambda: params),
                "opt_state": jax.eval_shape(new.init, params)}
        step, tree = ckpt.restore(tmp_path, like)
        assert step == 2
        _tree_equal(tree["opt_state"], s_ref)
        # continue both 2 more steps: identical trajectories
        s_new, p_new = tree["opt_state"], tree["params"]
        for _ in range(2):
            u_r, s_ref = O_ref.update(grads, s_ref, p_ref)
            u_n, s_new = new.update(grads, s_new, p_new)
            _tree_equal(u_r, u_n)
            p_ref = O.apply_updates(p_ref, u_r)
            p_new = O.apply_updates(p_new, u_n)
        _tree_equal(p_ref, p_new)
        _tree_equal(s_ref, s_new)


class TestPlanStoreTreeRoundTrip:
    """ISSUE 3 acceptance: plan.Plan round-trips through a checkpoint
    manifest as a StoreTree — the serialized form has no PolicyFn or
    SketchHParams.overrides, and the restored tree rebuilds the exact
    optimizer."""

    def _plan(self):
        from repro.plan import dense_budget_bytes, plan_for_params
        params = {"tok_embed": {"table": jnp.zeros((512, 64))},
                  "lm_head": {"table": jnp.zeros((384, 64))},
                  "w": jnp.zeros((32, 32))}
        plan = plan_for_params(params,
                               int(0.35 * dense_budget_bytes(params)),
                               width_multiple=16, min_rows=256)
        return params, plan

    def test_manifest_round_trip_rebuilds_exact_optimizer(self, tmp_path):
        from repro.core.stores import StoreTree
        params, plan = self._plan()
        opt = plan.make_optimizer(1e-2)
        state = opt.init(params)
        ckpt.save(tmp_path, 1, {"params": params, "opt_state": state},
                  extra={"plan": plan.to_json(),
                         "store_tree": plan.store_tree().to_json()})
        manifest = ckpt.read_manifest(tmp_path, 1)
        blob = json.dumps(manifest["extra"]["store_tree"])
        assert "policy" not in blob and "overrides" not in blob
        tree = StoreTree.from_json(manifest["extra"]["store_tree"])
        assert tree == plan.store_tree()
        # the restored StoreTree rebuilds the exact same optimizer
        rebuilt = O.adam_from_stores(1e-2, tree)
        _, grads = _setup()
        grads = jax.tree_util.tree_map(
            lambda p: jnp.sin(jnp.arange(p.size, dtype=jnp.float32)
                              ).reshape(p.shape), params)
        s_a, s_b = opt.init(params), rebuilt.init(params)
        for _ in range(3):
            u_a, s_a = opt.update(grads, s_a, params)
            u_b, s_b = rebuilt.update(grads, s_b, params)
            _tree_equal(u_a, u_b)
            _tree_equal(s_a, s_b)

    def test_fold_predicate_from_store_tree(self):
        """The Hokusai-fold predicate derived from the manifest StoreTree
        selects exactly the sketch moment leaves."""
        params, plan = self._plan()
        state = plan.make_optimizer(1e-2).init(params)
        pred = ckpt.is_sketch_from_store_tree(plan.store_tree())
        folded = ckpt.fold_sketches({"opt_state": state}, pred)["opt_state"]
        specs = plan.specs()
        assert specs   # the 0.35x budget must sketch something
        for path, d in specs.items():
            parts = path.split("/")
            for moment in d:
                leaf = folded[moment]
                for k in parts:
                    leaf = leaf[k]
                assert leaf.shape[1] == d[moment].width // 2
        # dense leaves untouched
        np.testing.assert_array_equal(np.asarray(folded["v"]["w"]),
                                      np.asarray(state["v"]["w"]))
