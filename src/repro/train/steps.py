"""Train-step factory: family dispatch + optimizer + sharding in one jit.

``make_train_step(cfg, ...)`` returns ``(init_fn, step_fn)``:

    params    = init_fn(rng)                       # or eval_shape'd
    step_fn(params, opt_state, batch) -> (params', opt_state', metrics)

``TrainStep.shardings(mesh)`` derives the full in/out sharding pytrees
(params per the rule table, optimizer state ZeRO-1 / sketch layout, batch
over the DP axes) so ``launch/dryrun.py`` and ``launch/train.py`` share
one code path.

Optimizer modes (paper §4 + baselines + beyond-paper):
    dense_adam      — full-size Adam (the paper's baseline)
    cs_adam         — Count-Sketch Adam, 1st+2nd moment sketched (CS-MV)
    cs_adam_v       — only the 2nd moment sketched (CS-V)
    cs_rmsprop      — β₁=0 Count-Min variant of Theorem 5.1 (extreme-scale)
    cs_adagrad      — Count-Min Adagrad (paper Alg. 3)
    cs_momentum     — Count-Sketch momentum (paper Alg. 2)
    lr_nmf_adam     — NMF rank-1 2nd-moment baseline (paper's LR-NMF-V)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import lowrank, optimizers as opt_lib
from repro.core.cleaning import CleaningSchedule
from repro.core.optimizers import SketchHParams, Transform
from repro.core.partition import SketchPolicy, nothing_policy
from repro.distributed import sharding as shd
from repro.models.config import ArchConfig
from repro.obs.profiling import scope


def family_module(cfg: ArchConfig):
    from repro.models import encdec, mamba, rwkv, transformer, vlm
    return {
        "gqa": transformer, "moe": transformer,
        "rwkv6": rwkv, "hybrid": mamba,
        "encdec": encdec, "vlm": vlm,
    }[cfg.family]


def build_optimizer(cfg: ArchConfig, mode: str, lr=1e-3,
                    cleaning: Optional[CleaningSchedule] = None,
                    kernel_backend: Optional[str] = None,
                    plan=None) -> Transform:
    """``kernel_backend`` selects the ``repro.kernels.registry`` backend
    for BOTH sketch hot paths: the sparse-rows (ids, rows) step and the
    dense whole-gradient fused ``update_read`` of every sketch-backed
    store (DESIGN.md §14) — None keeps the sparse path on 'auto' and the
    dense path on the composed chunked-scan fallback (bit-identical
    legacy numerics).

    ``plan``: a solved ``repro.plan.Plan`` — when given it supersedes the
    regex policy + global compression entirely (the plan's ``StoreTree``
    executes instead, via ``adam_from_stores``; DESIGN.md §12), with
    ``kernel_backend`` overriding the backend the plan carries.  Plans
    encode an Adam-family moment layout, so only the modes in
    ``repro.plan.MOMENT_MODES`` may be combined with one."""
    if plan is not None:
        from repro.plan import MOMENT_MODES
        if mode not in MOMENT_MODES:
            raise ValueError(
                f"optimizer mode {mode!r} cannot execute a memory plan "
                f"(Adam-family layouts only: {sorted(MOMENT_MODES)})")
        return plan.make_optimizer(lr, cleaning=cleaning,
                                   backend=kernel_backend)
    policy = SketchPolicy(min_rows=1024)
    hp = SketchHParams(compression=cfg.sketch_compression,
                       depth=cfg.sketch_depth,
                       backend=kernel_backend)
    if mode == "dense_adam":
        return opt_lib.adam(lr)
    if mode == "dense_adagrad":
        return opt_lib.adagrad(lr)
    if mode == "dense_momentum":
        return opt_lib.momentum(lr)
    if mode == "cs_adam":
        return opt_lib.countsketch_adam(lr, policy=policy, hparams=hp,
                                        cleaning=cleaning)
    if mode == "cs_adam_v":
        # CS-V: dense 1st moment, sketched 2nd — emulate by a policy split
        return opt_lib.countsketch_adam(
            lr, policy=policy, hparams=hp, cleaning=cleaning,
            track_first_moment=True, sketch_first_moment=False)
    if mode == "cs_rmsprop":
        return opt_lib.countsketch_rmsprop(lr, policy=policy, hparams=hp,
                                           cleaning=cleaning)
    if mode == "cs_adagrad":
        return opt_lib.countsketch_adagrad(lr, policy=policy, hparams=hp,
                                           cleaning=cleaning)
    if mode == "cs_momentum":
        return opt_lib.countsketch_momentum(lr, policy=policy, hparams=hp)
    if mode == "lr_nmf_adam":
        return lowrank.nmf_rank1_adam(lr, policy=policy)
    raise ValueError(f"unknown optimizer mode {mode!r}")


@dataclasses.dataclass
class TrainStep:
    cfg: ArchConfig
    init_fn: Callable
    step_fn: Callable
    optimizer: Transform
    batch_template: Dict[str, Any]
    # the run's StoreTree (set when a memory plan executes) — makes the
    # optimizer-state sharding classification exact (DESIGN.md §13)
    store_tree: Any = None
    # manual data-parallel mode: step_fn is shard_map'd over this axis
    dp_axis: Optional[str] = None

    # -- shape trees (no allocation) ---------------------------------------
    def params_shape(self):
        return jax.eval_shape(self.init_fn, jax.random.PRNGKey(0))

    def opt_shape(self, params_shape=None):
        ps = params_shape if params_shape is not None else self.params_shape()
        return jax.eval_shape(self.optimizer.init, ps)

    # -- shardings ----------------------------------------------------------
    def shardings(self, mesh: Mesh, batch_specs: Dict[str, Any]):
        cfg = self.cfg
        ps = self.params_shape()
        os_ = self.opt_shape(ps)
        pspec = shd.param_specs(ps, mesh, fsdp=cfg.fsdp,
                                expert_sharding=cfg.expert_sharding)
        ospec = shd.opt_specs_for_state(os_, ps, mesh, fsdp=cfg.fsdp,
                                        expert_sharding=cfg.expert_sharding,
                                        store_tree=self.store_tree)
        bspec = jax.tree_util.tree_map(
            lambda s: shd.batch_spec(mesh, s.shape), batch_specs)
        mspec = P()  # metrics replicated
        return (shd.named(mesh, pspec), shd.named(mesh, ospec),
                shd.named(mesh, bspec), NamedSharding(mesh, mspec))


def make_train_step(cfg: ArchConfig, *, optimizer: str = "cs_adam",
                    lr=1e-3, remat: bool = True,
                    sampled_softmax: bool = False,
                    grad_clip: Optional[float] = 1.0,
                    cleaning: Optional[CleaningSchedule] = None,
                    kernel_backend: Optional[str] = None,
                    plan=None, dp_axis: Optional[str] = None) -> TrainStep:
    """``dp_axis``: manual data-parallel mode — the step body runs inside
    ``shard_map`` over that mesh axis with the batch sharded on dim 0,
    params/optimizer state replicated in the body, and the gradient
    moved by explicit ``pmean`` collectives.  The step must then be
    TRACED inside ``shd.active_mesh(mesh)`` (launch/train.py --dp does);
    per-replica loss is pmean'd so metrics match the global-batch step."""
    mod = family_module(cfg)
    opt = build_optimizer(cfg, optimizer, lr=lr, cleaning=cleaning,
                          kernel_backend=kernel_backend, plan=plan)
    clip = (opt_lib.clip_by_global_norm(grad_clip)
            if grad_clip is not None else (lambda g: g))

    def loss_fn(params, batch):
        return mod.train_loss(cfg, params, batch, remat=remat,
                              sampled_softmax=sampled_softmax)

    def step_body(params, opt_state, batch):
        with scope("obs.grad"):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        if dp_axis is not None:
            with scope("obs.collective"):
                loss = jax.lax.pmean(loss, dp_axis)
                grads = jax.tree_util.tree_map(
                    lambda g: jax.lax.pmean(g, dp_axis), grads)
        grads = clip(grads)
        with scope("obs.kernel"):
            updates, opt_state = opt.update(grads, opt_state, params)
        params = opt_lib.apply_updates(params, updates)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                          for g in jax.tree_util.tree_leaves(grads)))
        metrics = {"loss": loss.astype(jnp.float32), "grad_norm": gn}
        return params, opt_state, metrics

    if dp_axis is None:
        step_fn = step_body
    else:
        def step_fn(params, opt_state, batch):
            mesh = shd.current_mesh()
            if mesh is None:
                raise ValueError(
                    "dp_axis train steps must be traced inside "
                    "shd.active_mesh(mesh) — the shard_map needs the mesh")

            def inner(params, opt_state, batch):
                # mesh axes are manual here: the model's activation
                # sharding constraints must not fire
                with shd.manual_collectives():
                    return step_body(params, opt_state, batch)

            return jax.shard_map(
                inner, mesh=mesh, in_specs=(P(), P(), P(dp_axis)),
                out_specs=(P(), P(), P()),
                check_vma=False)(params, opt_state, batch)

    def init_fn(rng):
        return mod.init(rng, cfg)

    return TrainStep(cfg=cfg, init_fn=init_fn, step_fn=step_fn,
                     optimizer=opt, batch_template={},
                     store_tree=plan.store_tree() if plan is not None
                     else None,
                     dp_axis=dp_axis)


def resolve_sparse_stores(stores, path: str, shape: Tuple[int, int]):
    """Resolve a ``StoreTree`` (e.g. a planner ``Plan.store_tree()``) at
    ``path`` for one (n, d) table driven through the sparse-rows (ids,
    grad-rows) kernels.  Returns ``(m_store, v_store, track_first_moment)``
    with the kernel constraints enforced: the 2nd moment must be
    sketch-backed and the 1st moment a signed count-sketch or absent
    (β₁=0) — the tree's moment layout is authoritative.

    Shared by ``make_sparse_embedding_step`` and the extreme-
    classification workload (``repro.train.extreme``)."""
    m_store, v_store = stores.resolve(path, shape, jnp.float32)
    if v_store is None or v_store.kind not in ("countmin", "sketch"):
        raise ValueError(
            f"the sparse-rows pipeline needs a sketch-backed v store "
            f"at {path!r}; the StoreTree resolved "
            f"{None if v_store is None else v_store.kind!r} — plan a "
            f"sketch for this table or drop `stores`")
    if m_store is not None and m_store.kind != "sketch":
        raise ValueError(
            f"the sparse-rows kernels keep the 1st moment in a signed "
            f"count-sketch or drop it (β₁=0); the StoreTree resolved a "
            f"{m_store.kind!r} m store at {path!r} — use "
            f"track_first_moment=False or a sketch-m plan")
    return m_store, v_store, m_store is not None


def sparse_embedding_stores(n_rows: int, dim: int, *,
                            hparams: Optional[SketchHParams] = None,
                            track_first_moment: bool = True,
                            cleaning: Optional[CleaningSchedule] = None,
                            path: str = "sparse_embedding", stores=None,
                            sketch_shards: int = 1,
                            shard_layout: str = "width"):
    """The (m_store, v_store) codec pair a ``make_sparse_embedding_step``
    called with the same table arguments binds — same StoreTree-vs-
    hparams precedence, same cleaning guards.  Out-of-band consumers
    (the ``repro.obs`` table monitors) read and ``stats`` these against
    the live opt_state; keeping the derivation shared means they can
    never drift from the codecs the optimizer actually updates."""
    hp = hparams if hparams is not None else SketchHParams()
    m_store = v_store = None
    if stores is not None:
        m_store, v_store, track_first_moment = resolve_sparse_stores(
            stores, path, (n_rows, dim))
    m_store, v_store = opt_lib.sparse_rows_stores(
        (int(n_rows), int(dim)), path, hp,
        track_first_moment=track_first_moment, cleaning=cleaning,
        m_store=m_store, v_store=v_store)
    if sketch_shards > 1:
        # mirror sparse_rows_adam_sharded's re-stamping, so the monitors
        # see the same sharded specs (per-shard occupancy gauges)
        if m_store is not None:
            m_store = m_store.with_sharding(sketch_shards, shard_layout)
        v_store = v_store.with_sharding(sketch_shards, shard_layout)
    return m_store, v_store


def make_sparse_embedding_step(n_rows: int, dim: int, *, lr=1e-3,
                               b1: float = 0.9, b2: float = 0.999,
                               eps: float = 1e-8,
                               hparams: Optional[SketchHParams] = None,
                               track_first_moment: bool = True,
                               cleaning: Optional[CleaningSchedule] = None,
                               path: str = "sparse_embedding",
                               stores=None,
                               dp_axis: Optional[str] = None,
                               mesh: Optional[Mesh] = None,
                               error_feedback: bool = False,
                               dir_clip: Optional[float] = 10.0,
                               sketch_shards: int = 1,
                               shard_layout: str = "width",
                               shard_axis: str = "model"):
    """Train step for the (ids, grad-rows) regime — LM1B-style embedding /
    softmax tables and extreme classification, where per-step work is
    O(touched rows), not O(n).

    Returns ``(init_fn, step_fn, optimizer)``:

        table     = init_fn(rng)                  # (n_rows, dim) f32
        opt_state = optimizer.init()
        table', opt_state' = step_fn(table, opt_state, ids, grad_rows)

    The optimizer is ``sparse_rows_adam`` — ``scale_by_adam_rows`` over a
    count-sketch store pair, chained with ``scale_by_lr`` (DESIGN.md
    §12).  ``stores``: an optional ``repro.core.stores.StoreTree`` (e.g.
    a planner ``Plan.store_tree()``) resolved at ``path`` for this
    table's store pair, superseding the ``hparams`` sizing.  The step
    routes through the kernel backend named by ``hparams.backend`` (tiled
    Pallas pipeline on TPU, jnp oracle on CPU — see ``repro.kernels``).
    Duplicate ids in a batch are handled by the backend (dedup +
    segment-sum on the tiled path).

    ``dp_axis``: data-parallel mode (DESIGN.md §13) — ``step_fn`` becomes
    a ``shard_map`` over that mesh axis (``mesh``, or the active mesh at
    trace time): each replica gets a shard of the GLOBAL (ids, grad_rows)
    batch (dim 0 sharded over ``dp_axis``), sketches its local gradient,
    and the collectives move the (depth, width, dim) sketches plus the
    int32 ids — never the (k, d) rows.  The 1st-moment sketch state
    evolves exactly as the single-device step on the concatenated batch
    (count-sketch linearity); the 2nd moment misses the cross-replica
    square terms unless ``error_feedback=True`` adds the MicroAdam-style
    residual sketch, and ``dir_clip`` trust-clamps the emitted direction
    against sketch-estimator noise (``sketched_reduce.dp_adam_rows``;
    None disables) — on one device too, where the signed-median
    numerator is just as much an estimate (``scale_by_adam_rows``).  Sketch state is replicated in the shard_map body;
    at the jit level it stores sharded per ``sharding.opt_specs_for_state``
    (width over 'data', dim over 'model').

    ``sketch_shards > 1``: model-parallel sketches (DESIGN.md §17) — the
    sketch state is partitioned into width slabs over ``shard_axis``
    (layout 'width' or 'hash'; ``sparse_rows_adam_sharded``), the body
    runs per (dp × shard) device on its local slab, and the shard-axis
    routing psum assembles cross-shard query rows.  Composes with
    ``dp_axis`` (the PR 4 collectives then move slab-sized payloads).
    The mesh's ``shard_axis`` size must EQUAL ``sketch_shards`` — the
    slab each body instance sees must be one shard's worth — checked at
    call time against the wrap's mesh.
    """
    hp = hparams if hparams is not None else SketchHParams()
    m_store = v_store = None
    if stores is not None:
        # the tree's moment layout is authoritative: a β₁=0 plan
        # (m=None) must not be overridden by this function's default
        m_store, v_store, track_first_moment = resolve_sparse_stores(
            stores, path, (n_rows, dim))
    if sketch_shards > 1:
        opt = opt_lib.sparse_rows_adam_sharded(
            lr, b1=b1, b2=b2, eps=eps, shape=(n_rows, dim), path=path,
            shards=sketch_shards, shard_layout=shard_layout,
            shard_axis=shard_axis, dp_axis=dp_axis, hparams=hp,
            track_first_moment=track_first_moment, cleaning=cleaning,
            error_feedback=error_feedback, dir_clip=dir_clip,
            m_store=m_store, v_store=v_store)
    elif dp_axis is None:
        opt = opt_lib.sparse_rows_adam(
            lr, b1=b1, b2=b2, eps=eps, shape=(n_rows, dim), path=path,
            hparams=hp, track_first_moment=track_first_moment,
            cleaning=cleaning, m_store=m_store, v_store=v_store,
            dir_clip=dir_clip)
    else:
        opt = opt_lib.sparse_rows_adam_dp(
            lr, b1=b1, b2=b2, eps=eps, shape=(n_rows, dim), path=path,
            axis_name=dp_axis, hparams=hp,
            track_first_moment=track_first_moment, cleaning=cleaning,
            error_feedback=error_feedback, dir_clip=dir_clip,
            m_store=m_store, v_store=v_store)

    def init_fn(rng):
        scale = 1.0 / jnp.sqrt(jnp.asarray(dim, jnp.float32))
        return jax.random.normal(rng, (n_rows, dim), jnp.float32) * scale

    def local_step(table, opt_state, ids, grad_rows):
        with scope("obs.kernel"):
            updates, opt_state = opt.update(
                {"ids": ids, "rows": grad_rows}, opt_state)
        with scope("obs.apply"):
            table = opt_lib.apply_sparse_updates(table, updates)
        return table, opt_state

    if sketch_shards > 1:
        wrapped = shd.sharded_sparse_wrap(local_step, mesh=mesh,
                                          dp_axis=dp_axis,
                                          shard_axis=shard_axis)

        def step_fn(table, opt_state, ids, grad_rows):
            use_mesh = mesh if mesh is not None else shd.current_mesh()
            if use_mesh is not None:
                sizes = dict(zip(use_mesh.axis_names,
                                 use_mesh.devices.shape))
                if sizes.get(shard_axis) != sketch_shards:
                    raise ValueError(
                        f"sketch_shards={sketch_shards} needs the mesh's "
                        f"{shard_axis!r} axis to be exactly that size, "
                        f"got {sizes} — each shard_map body must see one "
                        f"shard's (depth, local_width, dim) slab")
            return wrapped(table, opt_state, ids, grad_rows)
    elif dp_axis is None:
        step_fn = local_step
    else:
        step_fn = shd.dp_sparse_wrap(local_step, mesh=mesh,
                                     dp_axis=dp_axis)

    return init_fn, step_fn, opt
