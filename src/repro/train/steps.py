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
    per-replica loss is pmean'd so metrics match the global-batch step.

    A sigmoid-routed config (``cfg.router``) moves its router bias buffers
    after the update, by the step's expert loads, and reports
    ``routed_here`` (the (token, slot) pairs the held experts computed).
    ``cfg.sparse_embedding`` trains ``tok_embed`` through the sparse-rows
    step (``sparse_lm_step``)."""
    if cfg.sparse_embedding:
        if plan is not None or dp_axis is not None or sampled_softmax \
                or grad_clip is not None or optimizer != "cs_adam":
            raise ValueError(
                "a sparse-embedding LM step takes optimizer 'cs_adam' with "
                "no plan, dp axis, sampled softmax or gradient clipping: "
                "the embedding's gradient leaves the tree as rows, so a "
                "global norm over the tree would miss it")
        return sparse_lm_step(cfg, lr=lr, remat=remat, cleaning=cleaning,
                              kernel_backend=kernel_backend)
    mod = family_module(cfg)
    opt = build_optimizer(cfg, optimizer, lr=lr, cleaning=cleaning,
                          kernel_backend=kernel_backend, plan=plan)
    clip = (opt_lib.clip_by_global_norm(grad_clip)
            if grad_clip is not None else (lambda g: g))
    routed = cfg.router == "sigmoid"

    def loss_fn(params, batch):
        if routed:
            return mod.train_loss_stats(cfg, params, batch, remat=remat,
                                        sampled_softmax=sampled_softmax)
        return mod.train_loss(cfg, params, batch, remat=remat,
                              sampled_softmax=sampled_softmax), {}

    def step_body(params, opt_state, batch):
        with scope("obs.grad"):
            (loss, stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        if dp_axis is not None:
            with scope("obs.collective"):
                loss = jax.lax.pmean(loss, dp_axis)
                grads = jax.tree_util.tree_map(
                    lambda g: jax.lax.pmean(g, dp_axis), grads)
                stats = jax.tree_util.tree_map(
                    lambda x: jax.lax.psum(x, dp_axis), stats)
        grads = clip(grads)
        with scope("obs.kernel"):
            updates, opt_state = opt.update(grads, opt_state, params)
        params = opt_lib.apply_updates(params, updates)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                          for g in jax.tree_util.tree_leaves(grads)))
        metrics = {"loss": loss.astype(jnp.float32), "grad_norm": gn}
        if routed:
            params, metrics["routed_here"] = _after_routing(cfg, params,
                                                            stats)
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


def _after_routing(cfg: ArchConfig, params, stats):
    """Move the router bias buffers by the step's expert loads; return
    the params and ``routed_here``."""
    from repro.models import transformer
    with scope("obs.router"):
        params = transformer.with_router_bias(cfg, params, stats["loads"])
    return params, stats["routed_here"].astype(jnp.float32)


EMBED_PATH = "tok_embed/table"


def sparse_lm_step(cfg: ArchConfig, *, lr=1e-3, remat: bool = True,
                   cleaning: Optional[CleaningSchedule] = None,
                   kernel_backend: Optional[str] = None) -> TrainStep:
    """The LM step whose embedding table trains in the paper's (ids,
    grad-rows) regime.  The looked-up rows are gathered in float32 and
    cast; the gradient with respect to them goes, with the ids, through
    ``make_sparse_embedding_step``'s own step (dedup, count-sketch Adam
    kernel, table write: scopes ``obs.dedup``, ``obs.kernel``,
    ``obs.apply``).  Every other leaf, the output head included, takes
    ``build_optimizer(cfg, 'cs_adam')`` (the head's moments in sketches,
    the rest dense).  State: ``{'rest': ..., 'tok_embed': ...}``."""
    mod = family_module(cfg)
    rest_opt = build_optimizer(cfg, "cs_adam", lr=lr, cleaning=cleaning,
                               kernel_backend=kernel_backend)
    hp = SketchHParams(compression=cfg.sketch_compression,
                       depth=cfg.sketch_depth, backend=kernel_backend)
    _, embed_step, embed_opt = make_sparse_embedding_step(
        cfg.vocab, cfg.d_model, lr=lr, hparams=hp, path=EMBED_PATH)
    routed = cfg.router == "sigmoid"

    def split(params):
        return params["tok_embed"]["table"], {
            k: v for k, v in params.items() if k != "tok_embed"}

    def init(params):
        return {"rest": rest_opt.init(split(params)[1]),
                "tok_embed": embed_opt.init()}

    def update(*_):
        raise NotImplementedError("the sparse-embedding LM step applies "
                                  "its own updates")

    def step_fn(params, opt_state, batch):
        table, rest = split(params)
        b, s = batch["tokens"].shape
        ids = batch["tokens"].reshape(-1).astype(jnp.int32)
        rows = table[ids]

        def loss_fn(rest, rows):
            x = rows.reshape(b, s, -1).astype(cfg.dtype)
            return mod.train_loss_stats(cfg, rest, batch, remat=remat, x=x)

        with scope("obs.grad"):
            (loss, stats), (g_rest, g_rows) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(rest, rows)
        with scope("obs.kernel"):
            updates, rest_state = rest_opt.update(g_rest, opt_state["rest"],
                                                  rest)
        with scope("obs.apply"):
            rest = opt_lib.apply_updates(rest, updates)
        table, embed_state = embed_step(table, opt_state["tok_embed"], ids,
                                        g_rows)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                          for g in jax.tree_util.tree_leaves(
                              (g_rest, g_rows))))
        metrics = {"loss": loss.astype(jnp.float32), "grad_norm": gn}
        if routed:
            rest, metrics["routed_here"] = _after_routing(cfg, rest, stats)
        params = dict(rest, tok_embed={"table": table})
        return params, {"rest": rest_state, "tok_embed": embed_state}, \
            metrics

    return TrainStep(cfg=cfg, init_fn=lambda rng: mod.init(rng, cfg),
                     step_fn=step_fn, optimizer=Transform(init, update),
                     batch_template={})


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
