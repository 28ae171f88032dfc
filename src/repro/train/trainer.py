"""Fault-tolerant training loop.

Composes the pieces the launcher needs: jit'd step, deterministic data,
atomic/async checkpoints, per-phase host spans, and crash recovery (via
``repro.distributed.elastic.recovery_loop``).  The loop is synchronous
SPMD (JAX semantics); fault tolerance is checkpoint/restart with the
deterministic pipeline replaying the exact stream — resumed runs are
bit-identical (tested in tests/test_trainer.py).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.checkpoint import store
from repro.obs.profiling import PhaseTimer, span


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 100
    ckpt_async: bool = True
    keep: int = 3
    log_every: int = 10


@dataclasses.dataclass
class TrainState:
    step: int
    params: Any
    opt_state: Any


class Trainer:
    """``fit`` runs [start, total); checkpoints; records step times.

    Every phase of a step runs in a ``profiling.span``: ``train.data``
    (``data.batch``), ``train.feed`` (the host-to-device copy),
    ``train.clean`` (with a cleaner), ``train.dispatch`` (the ``step_fn``
    call), ``train.wait`` (until the loss is ready), ``train.record``
    (the metric fetches, history and observer) and ``train.checkpoint``.
    Their wall times go to ``phases``: the observer's ``phase_timer`` when
    it has one, the trainer's own ``PhaseTimer`` otherwise.

    ``plan``: an optional ``repro.plan.Plan`` executing on this run (in
    place of a bare sketch policy).  Both the plan and its executable
    ``StoreTree`` form are recorded in every checkpoint manifest, so
    restore — including an elastic restore that Hokusai-folds the
    sketches onto a halved budget — reconstructs the exact per-leaf
    stores (``plan.fold()`` mirrors ``store.fold_sketches``; the
    serialized manifest speaks StoreTree, not PolicyFns/overrides).

    ``store_tree``: record an executable ``StoreTree`` in the manifests
    of a run with no memory plan (e.g. a DP sparse-table run built from
    bare stores) — it is what gives elastic restore the EXACT
    ``is_sketch_from_store_tree`` fold predicate instead of the name
    heuristic (``repro.distributed.elastic.elastic_restore``)."""

    def __init__(self, step_fn: Callable, data, tcfg: TrainerConfig,
                 fail_at: Optional[int] = None, plan=None,
                 store_tree=None, observer=None, cleaner=None):
        self.step_fn = step_fn
        self.data = data
        self.tcfg = tcfg
        self.history: List[Dict[str, float]] = []
        self.plan = plan
        self.store_tree = store_tree
        # optional repro.obs.RunObserver: gets every step's host-side
        # record + the live opt_state at log boundaries (sketch-health
        # telemetry, DESIGN.md §15); ``fit`` flushes + closes it on
        # successful completion (a crash-restart re-enters fit with the
        # observer still open, so no partial window is lost)
        self.observer = observer
        self.phases = getattr(observer, "phase_timer", None) or PhaseTimer()
        # optional repro.core.cleaning.AsyncCleaner: dispatches the §4
        # count-min decay BETWEEN steps (mode='async'), at the same
        # boundary the sync lax.cond keys on, so numerics stay
        # bit-identical while the decay's cost moves off the step
        # phase's critical section into its own 'train.clean' span
        self.cleaner = cleaner
        if plan is not None and store_tree is not None \
                and plan.store_tree() != store_tree:
            raise ValueError("Trainer got both a plan and a store_tree "
                             "that disagree — the manifest must record "
                             "ONE executable vocabulary")
        self._fail_at = fail_at       # test hook: simulate a crash
        self._pending_ckpt = None

    def _maybe_checkpoint(self, state: TrainState, force: bool = False):
        t = self.tcfg
        if t.ckpt_dir is None:
            return
        if force or (state.step % t.ckpt_every == 0 and state.step > 0):
            if self._pending_ckpt is not None:
                self._pending_ckpt.join()     # backpressure: one in flight
            tree = {"params": state.params, "opt_state": state.opt_state}
            extra = None
            if self.plan is not None:
                extra = {"plan": self.plan.to_json(),
                         "store_tree": self.plan.store_tree().to_json()}
            elif self.store_tree is not None:
                extra = {"store_tree": self.store_tree.to_json()}
            self._pending_ckpt = store.save(
                t.ckpt_dir, state.step, tree,
                async_=t.ckpt_async, keep=t.keep, extra=extra)

    def restore_or_init(self, init_state: TrainState,
                        shardings=None) -> TrainState:
        t = self.tcfg
        if t.ckpt_dir is None or store.latest_step(t.ckpt_dir) is None:
            return init_state
        tree_like = {"params": init_state.params,
                     "opt_state": init_state.opt_state}
        step, tree = store.restore(t.ckpt_dir, tree_like,
                                   shardings=shardings)
        if self.plan is None:
            saved = store.read_manifest(t.ckpt_dir, step).get("extra", {})
            if saved.get("plan") is not None:
                from repro.plan import Plan   # deferred: plan pulls configs
                self.plan = Plan.from_json(saved["plan"])
        return TrainState(step=step, params=tree["params"],
                          opt_state=tree["opt_state"])

    def fit(self, state: TrainState) -> TrainState:
        t = self.tcfg
        timer = self.phases
        while state.step < t.total_steps:
            if self._fail_at is not None and state.step == self._fail_at:
                self._fail_at = None          # fail once
                raise RuntimeError(f"injected failure at step {state.step}")
            with span("train.data", timer):
                batch = self.data.batch(state.step)
            with span("train.feed", timer):
                batch = {k: jax.numpy.asarray(v) for k, v in batch.items()}
            if self.cleaner is not None:
                with span("train.clean", timer):
                    # the upcoming step observes counter state.step + 1 —
                    # the boundary the sync schedule's in-step lax.cond
                    # keys on; dispatch is non-blocking (device dataflow
                    # orders the decay before the step's reads)
                    opt_state, _ = self.cleaner.maybe_dispatch(
                        state.opt_state, state.step + 1)
                    state = TrainState(step=state.step,
                                       params=state.params,
                                       opt_state=opt_state)
            t0 = time.perf_counter()
            with span("train.dispatch", timer):
                params, opt_state, metrics = self.step_fn(
                    state.params, state.opt_state, batch)
            with span("train.wait", timer):
                jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            state = TrainState(step=state.step + 1, params=params,
                               opt_state=opt_state)
            with span("train.record", timer):
                rec = {"step": state.step, "time_s": dt,
                       **{k: float(np.asarray(v))
                          for k, v in metrics.items()}}
                self.history.append(rec)
                if self.observer is not None:
                    self.observer.on_step(state.step, rec, state.opt_state)
            with span("train.checkpoint", timer):
                self._maybe_checkpoint(state)
        with span("train.checkpoint", timer):
            self._maybe_checkpoint(state, force=True)
            if self._pending_ckpt is not None:
                self._pending_ckpt.join()
        if self.observer is not None:
            self.observer.close(state.step, state.opt_state)
        return state
