"""Elastic scaling (host-side control plane).

JAX multi-host steps are synchronous SPMD programs: a dead host stalls
the whole pod.  Recovery is therefore a control loop *around* the
compiled step:

  * ``ElasticPlan`` — given the surviving host/chip count, picks the new
    mesh (largest power-of-two data axis that fits), and decides whether
    the count-sketch optimizer state must FOLD (halve width — Hokusai,
    paper §5) to fit the shrunken per-device memory.  Folding preserves
    the accumulated state, so recovery does not reset the optimizer.
  * ``recovery_loop`` — the restart-on-failure wrapper used by
    ``launch/train.py``: run steps, on failure restore the latest atomic
    checkpoint, rebuild the (possibly smaller) mesh, continue.

These are deliberately pure-python and unit-testable; the device-side
re-layout is ordinary checkpoint restore with new shardings
(``repro/checkpoint``).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional


def largest_pow2_leq(n: int) -> int:
    return 1 << (n.bit_length() - 1) if n > 0 else 0


@dataclasses.dataclass(frozen=True)
class ElasticPlan:
    """Resize decision after losing hosts/chips.

    ``data_axis``/``model_axis``: the new mesh shape.  The model axis is
    kept fixed (TP degree is baked into weight layouts; shrinking it
    requires a different partitioning, which we avoid mid-run) and the
    data axis absorbs the loss.  ``fold_sketch``: whether per-device
    memory shrank enough that the sketch should halve its width."""

    data_axis: int
    model_axis: int
    pods: int
    fold_sketch: bool

    @property
    def chips(self) -> int:
        return self.data_axis * self.model_axis * self.pods


def plan_resize(available_chips: int, *, model_axis: int = 16,
                old_data_axis: int = 16, pods: int = 1,
                memory_headroom: float = 0.85) -> ElasticPlan:
    """New mesh after failures: keep TP fixed, shrink DP to the largest
    power of two that fits the surviving chips.  If per-device state grows
    by ≥ 1/headroom (fewer devices hold the same bytes), fold the sketch."""
    if available_chips < model_axis:
        raise ValueError(
            f"cannot keep model_axis={model_axis} with {available_chips} chips")
    per_pod = available_chips // pods
    new_data = largest_pow2_leq(per_pod // model_axis)
    if new_data == 0:
        raise ValueError("not enough chips for even data=1")
    growth = old_data_axis / new_data
    return ElasticPlan(data_axis=new_data, model_axis=model_axis, pods=pods,
                       fold_sketch=growth > 1.0 / memory_headroom)


def elastic_restore(ckpt_dir, tree_like, plan: ElasticPlan, *,
                    store_tree=None, shardings=None):
    """Checkpoint restore onto a (possibly shrunken) fleet, honoring the
    resize decision: when ``plan.fold_sketch`` every count-sketch leaf of
    the restored tree is Hokusai-folded (width halved, upper half added
    into the lower — ``repro.checkpoint.store.fold_sketches``), so the
    accumulated optimizer state survives the memory loss without reset.

    Sketch leaves are identified EXACTLY via ``is_sketch_from_store_tree``
    when a ``store_tree`` is given or the checkpoint manifest recorded one
    (planned runs always do); otherwise the name heuristic applies.
    Returns ``(step, tree, folded)``."""
    from repro.checkpoint import store as ckpt

    step, tree = ckpt.restore(ckpt_dir, tree_like, shardings=shardings)
    if not plan.fold_sketch:
        return step, tree, False
    if store_tree is not None:
        pred = ckpt.is_sketch_from_store_tree(store_tree)
    else:
        pred = ckpt.fold_predicate_from_manifest(
            ckpt.read_manifest(ckpt_dir, step))
    return step, ckpt.fold_sketches(tree, pred), True


@dataclasses.dataclass
class RecoveryOutcome:
    steps_run: int
    restarts: int
    final_step: int


def recovery_loop(run_steps: Callable[[int, int], int],
                  restore: Callable[[], int],
                  *, total_steps: int, max_restarts: int = 10,
                  on_failure: Optional[Callable[[Exception], None]] = None
                  ) -> RecoveryOutcome:
    """Restart-on-failure driver.

    ``run_steps(start, total)`` runs the training loop and returns the
    last completed step (it raises on simulated/real failure).
    ``restore()`` reloads the latest checkpoint and returns its step.
    Deterministic data pipelines (repro/data) make the replayed steps
    bit-identical."""
    restarts = 0
    step = restore()
    while step < total_steps:
        try:
            step = run_steps(step, total_steps)
        except Exception as e:  # noqa: BLE001 — any failure triggers recovery
            restarts += 1
            if on_failure is not None:
                on_failure(e)
            if restarts > max_restarts:
                raise
            step = restore()
    return RecoveryOutcome(steps_run=step, restarts=restarts, final_step=step)
