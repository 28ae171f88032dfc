"""Extreme-classification workload: MACH + sampled softmax at table scale.

The paper's headline systems result (§7.3, Table 8) trains a 49.5M-class
Amazon task with the β₁=0 Count-Min optimizer of Theorem 5.1 and spends
the freed optimizer memory on a 3.5× mini-batch.  This module builds that
regime end to end on the repo's substrate:

  * **MACH** (``core.hashing.mach_class_hash``): ``n_replicas``
    independent meta-classifiers, each mapping the ``n_classes`` true
    labels into an ``n_meta``-row output table — the 1M–50M-row table the
    sweep drives;
  * **sampled softmax**: per step each replica scores the positive
    meta-class against ``n_negatives`` shared zipf-sampled candidates, so
    the loss (and its gradient) touches O(B·nnz + B + n_negatives) table
    rows, never O(n_meta) — gradients are materialized as (ids, rows)
    and duplicate ids merge through ``kernels/dedup.py``;
  * **optimizer**: the PR-3 sparse-rows transforms — ``sparse_rows_adam``
    (kernel-backend routed) or its PR-4 DP form, with store sizing solved
    by the PR-2 planner (``plan_extreme`` → ``plan_for_tables``), or
    ``dense_rows_adam`` (below) as the memory-limited baseline in the
    SAME (ids, rows) calling convention, so the batch sweep compares like
    for like.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import optimizers as opt_lib
from repro.core import transforms as T
from repro.core.hashing import mach_class_hash
from repro.core.optimizers import SketchHParams, Transform, _with_lr
from repro.data import ExtremeConfig
from repro.distributed import sharding as shd
from repro.kernels import dedup
from repro.obs.profiling import scope
from repro.train.steps import resolve_sparse_stores

# optimizer modes the sparse-rows kernels can execute: β₁=0 CMS (the
# paper's extreme-scale choice), CS-MV Adam, and the dense baseline.
# cs_adam_v is absent by construction — its dense 1st moment has no
# sparse-rows form (resolve_sparse_stores would reject it anyway).
EXTREME_OPTIMIZERS = ("dense_adam", "cs_rmsprop", "cs_adam")

TABLE_PATHS = ("tok_embed/table", "class_head/table")


@dataclasses.dataclass(frozen=True)
class MachConfig:
    """The workload's single source of truth: true-label space, MACH
    reduction, feature space, and the sampled-softmax candidate counts.

    ``n_meta`` is the OUTPUT TABLE the optimizer state lives over — the
    quantity the ISSUE's "1M–50M-row table" names; ``n_classes`` may be
    far larger (MACH hashes it down per replica)."""

    n_classes: int
    n_meta: int
    n_features: int
    dim: int = 64
    n_replicas: int = 2
    nnz: int = 16
    n_negatives: int = 1024
    alpha: float = 1.05
    seed: int = 0

    def data_config(self, batch: int) -> ExtremeConfig:
        return ExtremeConfig(
            n_features=self.n_features, n_classes=self.n_classes,
            batch=batch, nnz=self.nnz, n_negatives=self.n_negatives,
            alpha=self.alpha, seed=self.seed)

    def table_shapes(self) -> Dict[str, Tuple[int, int]]:
        return {"tok_embed/table": (self.n_features, self.dim),
                "class_head/table": (self.n_meta, self.dim)}

    def class_maps(self) -> np.ndarray:
        """(n_replicas, n_classes) int32 — replica r's true-label →
        meta-class map (independent hash families per replica)."""
        return np.stack([
            mach_class_hash(seed=self.seed + 101 * r,
                            num_classes=self.n_classes,
                            num_buckets=self.n_meta, num_hashes=1)[0]
            for r in range(self.n_replicas)])


def plan_extreme(cfg: MachConfig, budget, *, optimizer: str = "cs_rmsprop",
                 backend: Optional[str] = None, depth: int = 3,
                 width_multiple: int = 256, seed: int = 0,
                 sketch_dtype: str = "float32"):
    """Solve the aux-memory plan for the workload's two tables under
    ``budget`` (bytes or any ``parse_budget`` string) — both tables carry
    the stream's real zipf exponent as traffic stats, so the water-fill
    splits width by actual volume × traffic, not by name.
    ``sketch_dtype`` sizes the plan at that cell dtype (int8 roughly
    quadruples solved widths at equal bytes — DESIGN.md §18)."""
    from repro.plan import TableStats, plan_for_tables
    stats = {p: TableStats(alpha=cfg.alpha) for p in TABLE_PATHS}
    plan = plan_for_tables(cfg.table_shapes(), budget, optimizer=optimizer,
                           stats=stats, default_alpha=cfg.alpha, depth=depth,
                           width_multiple=width_multiple, seed=seed,
                           sketch_dtype=sketch_dtype)
    return plan.with_backend(backend) if backend else plan


def dense_rows_adam(lr, b1: float = 0.9, b2: float = 0.999,
                    eps: float = 1e-8, *,
                    shape: Tuple[int, int]) -> Transform:
    """Dense Adam in the (ids, rows) calling convention — the baseline arm
    of the batch sweep.  Full (n, d) m/v buffers (the memory the sketch
    arm frees), but per-step WORK stays O(touched rows): duplicates merge
    through ``dedup_rows`` and only the unique rows' moments move.  Same
    legacy ``{"step", "m", "v"}`` state layout and ``scale_by_lr``
    terminal as ``sparse_rows_adam``, so the two arms are drop-in
    interchangeable in ``make_extreme_step``."""
    n, d = int(shape[0]), int(shape[1])

    def init(params=None):
        return {"step": jnp.zeros((), jnp.int32),
                "m": jnp.zeros((n, d), jnp.float32),
                "v": jnp.zeros((n, d), jnp.float32)}

    def update(grads, state, params=None):
        ids, rows = grads["ids"], grads["rows"]
        db = dedup.dedup_rows(ids, rows)
        live = db.mask[:, None]                     # (k, 1) f32
        # padding slots carry fill_id=-1: clamp them onto row 0 with a
        # zero delta so the gather/scatter never walks off the table
        uids = jnp.where(db.mask > 0, db.unique_ids, 0)
        g = db.rows
        step = state["step"] + 1
        t = step.astype(jnp.float32)
        m_old = state["m"][uids]
        v_old = state["v"][uids]
        dm = live * (1.0 - b1) * (g - m_old)
        dv = live * (1.0 - b2) * (g * g - v_old)
        m = state["m"].at[uids].add(dm)
        v = state["v"].at[uids].add(dv)
        mhat = (m_old + dm) / (1.0 - b1 ** t)
        vhat = jnp.maximum(v_old + dv, 0.0) / (1.0 - b2 ** t)
        # raw ascent direction (lr=-1.0 convention); scale_by_lr flips it
        direction = live * mhat / (jnp.sqrt(vhat) + eps)
        return ({"ids": ids, "rows": dedup.scatter_back(db, direction)},
                {"step": step, "m": m, "v": v})

    return _with_lr(Transform(init, update), lr)


def mach_log_scores(logits_list, class_maps, candidates) -> np.ndarray:
    """MACH inference aggregation (paper §7.3): per-replica meta-class
    LOG-SOFTMAX summed over replicas at the candidate classes.

    ``logits_list``: per replica, (B, n_meta) raw meta logits;
    ``class_maps``: per replica, (n_classes,) label → meta-class map;
    ``candidates``: (C,) candidate class ids.  Returns (B, C) scores.

    Raw-logit summation is miscalibrated — replicas with larger logit
    SCALES dominate the vote even when they carry no more information;
    log-probabilities are shift- and scale-calibrated (adding a constant
    per example changes nothing; see tests/test_extreme.py)."""
    agg = None
    for logits, cmap in zip(logits_list, class_maps):
        logits = np.asarray(logits, np.float64)
        mx = logits.max(axis=-1, keepdims=True)
        logz = mx + np.log(np.exp(logits - mx).sum(axis=-1, keepdims=True))
        logp = logits - logz                        # (B, n_meta)
        scores = logp[:, np.asarray(cmap)[np.asarray(candidates)]]
        agg = scores if agg is None else agg + scores
    return agg


def unique_id_ratio(ids: jnp.ndarray) -> jnp.ndarray:
    """Fraction of distinct ids in a gradient batch — the dedup/segment-
    sum pre-pass merges the rest, so this ratio IS the work reduction the
    dedup stage buys (telemetry: ``dedup_ratio`` in step metrics).  Sort-
    based, O(k log k), jit-safe at static k."""
    s = jnp.sort(ids.reshape(-1))
    n_unique = 1 + jnp.sum((s[1:] != s[:-1]).astype(jnp.int32))
    return n_unique.astype(jnp.float32) / s.shape[0]


def _sampled_softmax_loss(emb_rows, pos_w, neg_w):
    """(B, nnz, d) gathered embedding rows + (B, d)/(neg, d) gathered head
    rows → mean sampled-softmax NLL with the positive in slot 0.  Shared
    negatives keep the logits (B, 1+neg) — linear in B, which is what
    makes the batch sweep's memory story about OPTIMIZER state."""
    emb = emb_rows.sum(axis=1)                                 # (B, d)
    pos = jnp.sum(emb * pos_w, axis=-1)                        # (B,)
    neg = emb @ neg_w.T                                        # (B, neg)
    logits = jnp.concatenate([pos[:, None], neg], axis=1)
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - logits[:, 0])


def make_extreme_step(cfg: MachConfig, *, optimizer: str = "cs_rmsprop",
                      lr=1e-3, hparams: Optional[SketchHParams] = None,
                      plan=None, backend: Optional[str] = None,
                      dp_axis: Optional[str] = None,
                      mesh: Optional[Mesh] = None,
                      error_feedback: bool = False,
                      dir_clip: Optional[float] = 10.0):
    """One MACH replica's train step over the (ids, rows) substrate.

    Returns ``(init_fn, step_fn, opts)``:

        params     = init_fn(rng)      # {"tok_embed"/"class_head": {"table"}}
        opt_state  = {path: opt.init() for path, opt in opts.items()}
        params', opt_state', metrics = step_fn(params, opt_state, batch)

    ``batch``: ``features`` (B, nnz) int32, ``labels`` (B,) int32 and
    ``negatives`` (n_negatives,) int32 — labels/negatives ALREADY mapped
    to meta-class ids (the host applies ``cfg.class_maps()[r]``).

    ``plan`` (a ``plan_extreme`` result) pins both tables' stores through
    ``resolve_sparse_stores``; otherwise ``hparams`` sizes them.
    ``backend`` overrides the kernel backend either way.  ``dp_axis``
    runs the whole step as a ``shard_map`` over that axis: features and
    labels sharded on dim 0, negatives replicated, the gradient
    collective moving (depth, width, dim) sketches (DESIGN.md §13)."""
    if optimizer not in EXTREME_OPTIMIZERS:
        raise ValueError(
            f"extreme workload optimizers are {EXTREME_OPTIMIZERS}; "
            f"{optimizer!r} has no (ids, rows) form")
    if optimizer == "dense_adam":
        if plan is not None:
            raise ValueError("dense_adam is the no-plan baseline — a "
                             "memory plan under it would silently compress "
                             "the run it is compared against")
        if dp_axis is not None:
            raise ValueError(
                "dense_adam has no sketched all-reduce (moving dense (k, d)"
                " rows is the cost DP avoids) — run it without dp_axis")
    hp = hparams if hparams is not None else SketchHParams(compression=100.0)
    if backend:
        hp = dataclasses.replace(hp, backend=backend)
    track = optimizer == "cs_adam"
    b1 = 0.9 if (track or optimizer == "dense_adam") else 0.0
    stores = None
    if plan is not None:
        if bool(plan.track_first_moment) != track:
            raise ValueError(
                f"plan moment layout (track_first_moment="
                f"{plan.track_first_moment}) does not match optimizer "
                f"{optimizer!r} — solve the plan with optimizer={optimizer!r}")
        stores = plan.store_tree()
        if backend:
            stores = stores.with_backend(backend)

    opts: Dict[str, Transform] = {}
    for path, shape in cfg.table_shapes().items():
        if optimizer == "dense_adam":
            opts[path] = dense_rows_adam(lr, b1=b1, shape=shape)
            continue
        m_store = v_store = None
        if stores is not None:
            m_store, v_store, track = resolve_sparse_stores(
                stores, path, shape)
        if dp_axis is None:
            opts[path] = opt_lib.sparse_rows_adam(
                lr, b1=b1, shape=shape, path=path, hparams=hp,
                track_first_moment=track, m_store=m_store, v_store=v_store,
                dir_clip=dir_clip)
        else:
            opts[path] = opt_lib.sparse_rows_adam_dp(
                lr, b1=b1, shape=shape, path=path, axis_name=dp_axis,
                hparams=hp, track_first_moment=track,
                error_feedback=error_feedback, dir_clip=dir_clip,
                m_store=m_store, v_store=v_store)

    def init_fn(rng):
        ke, kh = jax.random.split(rng)
        scale = 1.0 / jnp.sqrt(jnp.asarray(cfg.dim, jnp.float32))
        return {
            "tok_embed": {"table": jax.random.normal(
                ke, (cfg.n_features, cfg.dim), jnp.float32) * scale},
            "class_head": {"table": jax.random.normal(
                kh, (cfg.n_meta, cfg.dim), jnp.float32) * scale},
        }

    def local_step(params, opt_state, batch):
        feats = batch["features"].astype(jnp.int32)            # (B, nnz)
        labels = batch["labels"].astype(jnp.int32)             # (B,)
        negs = batch["negatives"].astype(jnp.int32)            # (neg,)
        emb_rows = params["tok_embed"]["table"][feats]         # (B, nnz, d)
        pos_w = params["class_head"]["table"][labels]          # (B, d)
        neg_w = params["class_head"]["table"][negs]            # (neg, d)
        loss, (g_emb, g_pos, g_neg) = jax.value_and_grad(
            _sampled_softmax_loss, argnums=(0, 1, 2))(emb_rows, pos_w, neg_w)
        if dp_axis is not None:
            loss = jax.lax.pmean(loss, dp_axis)
        grads = {
            "tok_embed/table": {
                "ids": feats.reshape(-1),
                "rows": g_emb.reshape(-1, cfg.dim)},
            "class_head/table": {
                "ids": jnp.concatenate([labels, negs]),
                "rows": jnp.concatenate([g_pos, g_neg])},
        }
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g["rows"]))
                          for g in grads.values()))
        with scope("obs.dedup"):
            dr = sum(unique_id_ratio(g["ids"])
                     for g in grads.values()) / len(grads)
        if dp_axis is not None:
            # per-replica row count differs only by sharding; the norm is
            # over the GLOBAL gradient, like the dense step's metric
            gn = jnp.sqrt(jax.lax.psum(jnp.square(gn), dp_axis))
            dr = jax.lax.pmean(dr, dp_axis)
        new_params = {"tok_embed": {}, "class_head": {}}
        new_state = {}
        for path, opt in opts.items():
            top, leaf = path.split("/")
            updates, new_state[path] = opt.update(grads[path],
                                                  opt_state[path])
            new_params[top][leaf] = opt_lib.apply_sparse_updates(
                params[top][leaf], updates)
        return new_params, new_state, {"loss": loss.astype(jnp.float32),
                                       "grad_norm": gn,
                                       "dedup_ratio": dr}

    if dp_axis is None:
        step_fn = local_step
    else:
        def step_fn(params, opt_state, batch):
            use_mesh = mesh if mesh is not None else shd.current_mesh()
            if use_mesh is None:
                raise ValueError(
                    "dp extreme steps need a mesh: pass mesh= or trace "
                    "inside shd.active_mesh(mesh)")
            dp = P(dp_axis)
            return jax.shard_map(
                local_step, mesh=use_mesh,
                in_specs=(P(), P(), {"features": dp, "labels": dp,
                                     "negatives": P()}),
                out_specs=(P(), P(), P()),
                check_vma=False)(params, opt_state, batch)

    return init_fn, step_fn, opts
