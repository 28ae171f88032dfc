"""Count-sketch first-order optimizers (paper §4, Algorithms 2–4) plus the
dense baselines they are measured against.

Since the store/transform refactor (DESIGN.md §12) this module is a thin
compatibility layer: the update rules live in ``repro.core.transforms``
(``scale_by_momentum`` / ``scale_by_adagrad`` / ``scale_by_adam`` /
``scale_by_rmsprop``), the storage codecs in ``repro.core.stores``
(``DenseStore`` / ``CountSketchStore`` / ``CountMinStore`` /
``Rank1Store``), and every entry point here is ``chain(rule,
scale_by_lr(lr))`` presented in the historical ``{"step", "m", "v"}``
state layout — so checkpoints, sharding rules, and manifests written by
the old API restore unchanged under the new one.

    opt = countsketch_adam(lr=1e-3, policy=SketchPolicy())   # legacy form
    opt = chain(clip_by_global_norm(1.0),                    # composable form
                scale_by_adam(m_store=CountSketchStore(compression=5.0),
                              v_store=CountMinStore(compression=5.0),
                              where=SketchPolicy()),
                scale_by_lr(1e-3))
    state = opt.init(params)
    updates, state = opt.update(grads, state, params)
    params = apply_updates(params, updates)

The legacy ``policy``/``rank1_policy``/``hparams.overrides`` triple
dispatch is bridged onto a ``StoreTree`` by ``stores_from_policy``;
moment *states* evolve bit-identically to the pre-refactor monoliths
(the parity grid in tests/test_legacy_parity.py pins this).  The one
numerical change is the final lr-scale association — ``-η·(x/denom)``
instead of ``(-η·x)/denom`` — a ≤1-ulp shift on emitted updates that
composability requires (DESIGN.md §12).

The per-row *sparse* fast path (``sparse_rows_adam`` /
``adam_sparse_rows``) is used by the sampled-softmax / embedding train
steps where the gradient is materialized as (ids, rows) instead of a
dense (n, d) array — computation then scales with the number of touched
rows, the regime the paper targets.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import sketch as cs
from repro.core import stores as stores_lib
from repro.core import transforms as T
from repro.core.cleaning import CleaningSchedule, maybe_clean
from repro.core.partition import PolicyFn, nothing_policy
from repro.core.stores import (  # noqa: F401  (public re-exports)
    AuxStore, CountMinStore, CountSketchStore, DenseStore, Rank1Moment,
    Rank1Store, StoreTree, leaf_seed as _leaf_seed)
from repro.core.transforms import (  # noqa: F401  (public re-exports)
    Schedule, Transform, _lr_at, _path_str, chain, clip_by_global_norm,
    scale_by_adagrad, scale_by_adam, scale_by_adam_rows,
    scale_by_adam_rows_dp, scale_by_lr, scale_by_momentum, scale_by_rmsprop,
    tree_map_with_path)


def apply_updates(params, updates):
    return jax.tree_util.tree_map(
        lambda p, u: (p + u.astype(p.dtype)) if u is not None else p,
        params, updates, is_leaf=lambda x: x is None)


@dataclasses.dataclass(frozen=True)
class SketchHParams:
    """How sketched leaves are sized.  ``compression`` is the total memory
    ratio n·d / (depth·width·d) — the paper's LM experiments use 5×, the
    extreme-classification experiment 100× (1% size).

    ``dense_chunk``: the dense-gradient path processes the n rows in
    chunks of this size inside one ``lax.scan`` — query(pre-step sketch),
    delta, scatter, and the direction row all fused per chunk, the
    XLA mirror of the Pallas ``cs_ema_tiled`` kernel.  Peak temp drops
    from O(depth·n·d) to O(depth·chunk·d).  0 disables chunking (the
    reference unchunked path; bit-identical results).

    Rows whose gradient is entirely zero always get NO parameter update
    (and no sketch write) — the paper's per-item algorithm only touches
    active features.  Without that, a zero-grad row's update is
    median-noise / sqrt(min-estimate ≈ 0), which diverges (observed:
    tests/test_optimizers.py::TestConvergence).

    ``backend``: which kernel backend sketch ops run on — a name
    registered in ``repro.kernels.registry`` ('ref' | 'xla' | 'tiled' |
    'interpret') or 'auto' for the per-host best (tiled on TPU, xla
    elsewhere).  Routes BOTH the sparse-rows fast path (DESIGN.md §10)
    and the dense-path fused ``update_read`` of every sketch-backed
    store these hparams derive (the stores are created with
    ``backend=`` — DESIGN.md §14).  None keeps the sparse path on
    'auto' and the dense path on the composed fallback (bit-identical
    legacy numerics).  Any other name raises ``KeyError`` on either
    path when the step traces.

    ``overrides``: per-path (depth, width) assignments.  Legacy hook; new
    code pins per-leaf specs through a ``StoreTree`` instead (the
    planner's ``Plan.store_tree()`` — DESIGN.md §12).  A tuple-of-tuples
    (not a dict) so the dataclass stays hashable.

    ``dtype``: element type of the sketch arrays ('float32' | 'bfloat16'
    | ...).  ``SketchSpec.nbytes`` is dtype-aware, so the planner's byte
    accounting and the allocated state agree for bf16 sketches too."""
    compression: float = 5.0
    depth: int = 3
    width_multiple: int = 256
    seed: int = 0
    identity: bool = False    # exact-table test mode
    strict_paper: bool = False  # 3-pass query→update→query semantics
    dense_chunk: int = 8192
    backend: Optional[str] = None
    dtype: str = "float32"
    overrides: Tuple[Tuple[str, Tuple[int, int]], ...] = ()

    def override_for(self, path: str) -> Optional[Tuple[int, int]]:
        for p, dw in self.overrides:
            if p == path:
                return dw
        return None

    def spec(self, path: str, shape, *, signed: bool) -> cs.SketchSpec:
        dw = self.override_for(path)
        if dw is not None:
            if len(shape) != 2:
                raise ValueError(f"sketch override at {path!r} needs a "
                                 f"rank-2 leaf, got {tuple(shape)}")
            depth, width = dw
            return cs.SketchSpec(depth=int(depth), width=int(width),
                                 dim=int(shape[1]), signed=signed,
                                 seed=_leaf_seed(path, self.seed),
                                 dtype=jnp.dtype(self.dtype),
                                 identity=self.identity)
        return cs.for_param(tuple(shape), compression=self.compression,
                            depth=self.depth, signed=signed,
                            seed=_leaf_seed(path, self.seed),
                            width_multiple=self.width_multiple,
                            dtype=jnp.dtype(self.dtype),
                            identity=self.identity)


# ---------------------------------------------------------------------------
# Legacy-layout adapter + policy → StoreTree bridge
# ---------------------------------------------------------------------------

def _with_lr(rule: Transform, lr: Schedule) -> Transform:
    """``chain(rule, scale_by_lr(lr))`` presented in the legacy state
    layout: the rule's own ``{"step", ...}`` dict IS the optimizer state
    (the lr link's step counter always equals the rule's, so it is
    reconstructed rather than stored — old checkpoints restore as-is)."""
    chained = T.chain(rule, T.scale_by_lr(lr))

    def init(params=None):
        state, _lr_state = chained.init(params)
        return state

    def update(grads, state, params=None):
        u, (state, _lr_state) = chained.update(
            grads, (state, {"step": state["step"]}), params)
        return u, state

    return Transform(init, update)


def stores_from_policy(policy: PolicyFn = nothing_policy, *,
                       rank1_policy: PolicyFn = nothing_policy,
                       hparams: SketchHParams = SketchHParams(),
                       cleaning: Optional[CleaningSchedule] = None,
                       track_first_moment: bool = True,
                       sketch_first_moment: bool = True,
                       rule: str = "adam") -> StoreTree:
    """Bridge the legacy ``PolicyFn``/``rank1_policy``/``overrides``
    triple dispatch onto a ``StoreTree``.  Per-leaf sketch specs (seed
    derivation included) are exactly what ``hparams.spec`` produced, so
    states are interchangeable with the pre-refactor monoliths.

    ``rule`` picks the slot layout: 'adam' fills (m, v); 'momentum' a
    signed sketch in the m slot only; 'adagrad' a count-min in the v
    slot only.  ``hparams.backend`` rides onto every sketch-backed store
    unchanged (its fused ``update_read`` backend — DESIGN.md §14)."""
    track = track_first_moment
    backend = hparams.backend

    def _dense_m():
        return DenseStore() if track else None

    if rule == "momentum":
        def resolver(path, shape):
            if policy(path, shape):
                return (CountSketchStore(
                    spec=hparams.spec(path, shape, signed=True),
                    backend=backend), None)
            return None
        return StoreTree(default_m=DenseStore(), default_v=None,
                         resolver=resolver)

    if rule == "adagrad":
        def resolver(path, shape):
            if policy(path, shape):
                return (None, CountMinStore(
                    spec=hparams.spec(path, shape, signed=False),
                    cleaning=cleaning, backend=backend))
            return None
        return StoreTree(default_m=None, default_v=DenseStore(),
                         resolver=resolver)

    if rule != "adam":
        raise ValueError(f"unknown rule {rule!r} (adam | momentum | adagrad)")

    def resolver(path, shape):
        if rank1_policy(path, shape):
            return (_dense_m(), Rank1Store())
        if policy(path, shape):
            if track and sketch_first_moment:
                m = CountSketchStore(
                    spec=hparams.spec(path, shape, signed=True),
                    backend=backend)
            else:
                m = _dense_m()
            return (m, CountMinStore(
                spec=hparams.spec(path, shape, signed=False),
                cleaning=cleaning, backend=backend))
        return None

    return StoreTree(default_m=_dense_m(), default_v=DenseStore(),
                     resolver=resolver)


def adam_from_stores(lr: Schedule, stores: StoreTree, *, b1: float = 0.9,
                     b2: float = 0.999, eps: float = 1e-8,
                     dense_chunk: int = 8192,
                     strict_paper: bool = False) -> Transform:
    """``chain(scale_by_adam(stores=...), scale_by_lr(lr))`` in the legacy
    ``{"step", "m", "v"}`` state layout — what the memory-budget planner
    executes (``plan.Plan.make_optimizer``) and what the benchmarks'
    ``--store`` axis drives."""
    return _with_lr(T.scale_by_adam(b1=b1, b2=b2, eps=eps, stores=stores,
                                    dense_chunk=dense_chunk,
                                    strict_paper=strict_paper), lr)


def adagrad_from_stores(lr: Schedule, stores: StoreTree, *,
                        eps: float = 1e-10, dense_chunk: int = 8192,
                        strict_paper: bool = False) -> Transform:
    """``chain(scale_by_adagrad(stores=...), scale_by_lr(lr))`` in the
    legacy ``{"step", "v"}`` state layout — the Alg. 3 companion of
    ``adam_from_stores`` for explicit store trees."""
    return _with_lr(T.scale_by_adagrad(eps, stores=stores,
                                       dense_chunk=dense_chunk,
                                       strict_paper=strict_paper), lr)


# ---------------------------------------------------------------------------
# Dense baselines (wrappers over the same rules, all-dense stores)
# ---------------------------------------------------------------------------

def sgd(lr: Schedule) -> Transform:
    return T.scale_by_lr(lr)


def momentum(lr: Schedule, gamma: float = 0.9) -> Transform:
    """Dense Polyak momentum: m ← γm + g ; x ← x − ηm."""
    return _with_lr(T.scale_by_momentum(gamma), lr)


def adagrad(lr: Schedule, eps: float = 1e-10) -> Transform:
    return _with_lr(T.scale_by_adagrad(eps), lr)


def adam(lr: Schedule, b1: float = 0.9, b2: float = 0.999,
         eps: float = 1e-8) -> Transform:
    return _with_lr(T.scale_by_adam(b1=b1, b2=b2, eps=eps), lr)


# ---------------------------------------------------------------------------
# Count-sketch optimizers (paper Algorithms 2, 3, 4)
# ---------------------------------------------------------------------------

def countsketch_momentum(lr: Schedule, gamma: float = 0.9, *,
                         policy: PolicyFn = nothing_policy,
                         hparams: SketchHParams = SketchHParams()) -> Transform:
    """Paper Alg. 2.  Linear form: m += (γ−1)·m_{t−1} + g."""
    stores = stores_from_policy(policy, hparams=hparams, rule="momentum")
    return _with_lr(T.scale_by_momentum(
        gamma, stores=stores, dense_chunk=hparams.dense_chunk,
        strict_paper=hparams.strict_paper), lr)


def countsketch_adagrad(lr: Schedule, eps: float = 1e-10, *,
                        policy: PolicyFn = nothing_policy,
                        hparams: SketchHParams = SketchHParams(),
                        cleaning: Optional[CleaningSchedule] = None) -> Transform:
    """Paper Alg. 3: cumulative squared gradient in a Count-Min sketch."""
    stores = stores_from_policy(policy, hparams=hparams, cleaning=cleaning,
                                rule="adagrad")
    return _with_lr(T.scale_by_adagrad(
        eps, stores=stores, dense_chunk=hparams.dense_chunk,
        strict_paper=hparams.strict_paper), lr)


def countsketch_adam(lr: Schedule, b1: float = 0.9, b2: float = 0.999,
                     eps: float = 1e-8, *,
                     policy: PolicyFn = nothing_policy,
                     rank1_policy: PolicyFn = nothing_policy,
                     hparams: SketchHParams = SketchHParams(),
                     cleaning: Optional[CleaningSchedule] = None,
                     track_first_moment: bool = True,
                     sketch_first_moment: bool = True) -> Transform:
    """Paper Alg. 4.  1st moment in a Count-Sketch (signed, median query);
    2nd moment in a Count-Min sketch (min query) with optional cleaning.

    ``track_first_moment=False`` gives the β₁=0 (RMSProp) variant of
    Theorem 5.1 — what the paper runs for the 49.5M-class Amazon task —
    where the 1st-moment state is dropped entirely (None leaves) for the
    sketched *and* dense parameters.  ``sketch_first_moment=False`` is the
    paper's "CS-V" ablation: dense 1st moment, sketched 2nd.

    ``rank1_policy`` selects leaves whose 2nd moment lives in a
    ``Rank1Store`` NMF factorization instead (1st moment dense), the
    LR-NMF-V baseline numerics of ``lowrank.nmf_rank1_adam`` — so one
    transform can execute a mixed dense / sketch / rank-1 memory plan
    (``repro.plan``).  It takes precedence over ``policy``."""
    stores = stores_from_policy(
        policy, rank1_policy=rank1_policy, hparams=hparams,
        cleaning=cleaning, track_first_moment=track_first_moment,
        sketch_first_moment=sketch_first_moment)
    return adam_from_stores(lr, stores, b1=b1, b2=b2, eps=eps,
                            dense_chunk=hparams.dense_chunk,
                            strict_paper=hparams.strict_paper)


def countsketch_rmsprop(lr: Schedule, b2: float = 0.999, eps: float = 1e-8, *,
                        policy: PolicyFn = nothing_policy,
                        hparams: SketchHParams = SketchHParams(),
                        cleaning: Optional[CleaningSchedule] = None) -> Transform:
    """The β₁=0 optimizer analyzed by Theorem 5.1 (Count-Min Sketch Adam
    without the 1st moment) — ``chain(scale_by_rmsprop(...),
    scale_by_lr(lr))``, bit-identical to
    ``countsketch_adam(track_first_moment=False)``."""
    stores = stores_from_policy(policy, hparams=hparams, cleaning=cleaning,
                                track_first_moment=False,
                                sketch_first_moment=False)
    return _with_lr(T.scale_by_rmsprop(
        b2=b2, eps=eps, stores=stores, dense_chunk=hparams.dense_chunk,
        strict_paper=hparams.strict_paper), lr)


# ---------------------------------------------------------------------------
# Sparse-row fast path — gradient given as (ids, rows); cost O(k·d), k = #rows
# ---------------------------------------------------------------------------

def adam_sparse_rows(spec_m: Optional[cs.SketchSpec], spec_v: cs.SketchSpec,
                     M: Optional[jnp.ndarray], V: jnp.ndarray,
                     ids: jnp.ndarray, g: jnp.ndarray, step: jnp.ndarray, *,
                     lr: Schedule, b1: float = 0.9, b2: float = 0.999,
                     eps: float = 1e-8,
                     cleaning: Optional[CleaningSchedule] = None,
                     strict_paper: bool = False,
                     backend: Optional[str] = None):
    """CS-Adam on ``k`` touched rows.  Returns (M', V', row_updates).

    The functional kernel-facing core (spec-level, lr fused) under the
    ``scale_by_adam_rows`` transform; ``spec_m``/``M`` may be None for
    the β₁=0 variant.

    ``backend`` routes the step through the kernel registry in
    ``repro.kernels`` ('ref' | 'xla' | 'tiled' | 'interpret', or 'auto'
    for the per-host best).  Registry backends handle duplicate ids
    themselves (xla and tiled dedup + segment-sum them; ref composes them
    through the EMA item by item) and return row updates such that
    ``params.at[ids].add(upd)`` is the correct application.

    ``backend=None`` keeps the in-graph XLA batch path below, where
    ``ids`` must be de-duplicated by the caller (use
    ``kernels.dedup.dedup_rows`` or ``jnp.unique`` with a fill id) — the
    paper's setting, where each active feature appears once per
    mini-batch.  ``strict_paper`` (3-pass semantics) only exists on the
    XLA path."""
    if backend is not None:
        if strict_paper:
            raise ValueError("strict_paper is only supported on the "
                             "default (backend=None) XLA path")
        from repro import kernels  # deferred: kernels imports this module's deps
        V_in = maybe_clean(cleaning, V, step)
        return kernels.adam_rows(spec_m, spec_v, M, V_in, ids, g, step,
                                 lr=lr, b1=b1, b2=b2, eps=eps,
                                 backend=backend)
    eta = _lr_at(lr, step)
    t = step.astype(jnp.float32)
    if spec_m is not None:
        m_old = cs.query(spec_m, M, ids)
        delta_m = (1.0 - b1) * (g - m_old)
        if strict_paper:
            M, m_new = cs.query_after_update(spec_m, M, ids, delta_m)
        else:
            M, m_new = cs.update_and_query(spec_m, M, ids, delta_m)
        mhat = m_new / (1.0 - b1 ** t)
    else:
        mhat = g
    V = maybe_clean(cleaning, V, step)
    v_old = cs.query(spec_v, V, ids)
    delta_v = (1.0 - b2) * (g * g - v_old)
    if strict_paper:
        V, v_new = cs.query_after_update(spec_v, V, ids, delta_v)
    else:
        V, v_new = cs.update_and_query(spec_v, V, ids, delta_v)
    v_new = jnp.maximum(v_new, 0.0)
    vhat = v_new / (1.0 - b2 ** t)
    upd = -eta * mhat / (jnp.sqrt(vhat) + eps)
    return M, V, upd


def sparse_rows_adam(lr: Schedule, b1: float = 0.9, b2: float = 0.999,
                     eps: float = 1e-8, *, shape: Tuple[int, int],
                     path: str = "sparse_rows",
                     hparams: SketchHParams = SketchHParams(),
                     track_first_moment: bool = True,
                     cleaning: Optional[CleaningSchedule] = None,
                     m_store: Optional[AuxStore] = None,
                     v_store: Optional[AuxStore] = None,
                     dir_clip: Optional[float] = None) -> Transform:
    """Optax-shaped CS-Adam for ONE (n, d) table fed (ids, rows) gradients
    — ``chain(scale_by_adam_rows(m_store=..., v_store=...),
    scale_by_lr(lr))`` in the legacy state layout.

    The transform owns the sketch state for a single embedding/softmax
    table whose gradients arrive as ``{"ids": (k,), "rows": (k, d)}`` —
    the sampled-softmax / extreme-classification regime where work scales
    with touched rows.  Each ``update`` routes through the kernel backend
    named by ``hparams.backend`` (DESIGN.md §10), so the same training code
    runs the jnp oracle on CPU and the tiled Pallas pipeline on TPU.

    ``m_store``/``v_store`` override the ``hparams``-derived stores (any
    bound ``CountSketchStore``/``CountMinStore``, e.g. from a planner
    ``StoreTree``).  ``track_first_moment=False`` is the β₁=0 (Theorem
    5.1 / RMSProp) variant the paper uses for the 49.5M-class Amazon
    task.  ``dir_clip``: per-coordinate direction trust clamp
    (``transforms.scale_by_adam_rows``); None leaves it unclamped."""
    if hparams.strict_paper:
        raise ValueError("sparse_rows_adam always runs through the kernel "
                         "registry, which has no strict_paper (3-pass) "
                         "path — use adam_sparse_rows(backend=None, "
                         "strict_paper=True) instead")
    m_store, v_store = _sparse_rows_stores(
        shape, path, hparams, track_first_moment=track_first_moment,
        cleaning=cleaning, m_store=m_store, v_store=v_store)
    # a backend pinned on the store itself (e.g. by a planner StoreTree /
    # --store-backend) wins over the hparams knob
    backend = getattr(v_store, "backend", None) or hparams.backend
    rule = T.scale_by_adam_rows(
        b1=b1, b2=b2, eps=eps, m_store=m_store, v_store=v_store,
        backend=backend if backend is not None else "auto",
        dir_clip=dir_clip)
    return _with_lr(rule, lr)


def sparse_rows_adam_dp(lr: Schedule, b1: float = 0.9, b2: float = 0.999,
                        eps: float = 1e-8, *, shape: Tuple[int, int],
                        path: str = "sparse_rows",
                        axis_name: str = "data",
                        hparams: SketchHParams = SketchHParams(),
                        track_first_moment: bool = True,
                        cleaning: Optional[CleaningSchedule] = None,
                        error_feedback: bool = False,
                        dir_clip: Optional[float] = 10.0,
                        m_store: Optional[AuxStore] = None,
                        v_store: Optional[AuxStore] = None) -> Transform:
    """Data-parallel ``sparse_rows_adam``: identical store derivation and
    legacy ``{"step", "m", "v", "residual"}`` state layout, but ``update``
    must run inside ``shard_map``/``vmap(axis_name=...)`` over
    ``axis_name`` — the collective all-reduces the (depth, width, dim)
    gradient sketches instead of the (k, d) rows (DESIGN.md §13).

    ``error_feedback=True`` adds the residual sketch that accumulates the
    2nd-moment cross-replica term.  The emitted ``{"ids", "rows"}`` are
    at the GLOBAL unique ids (out-of-range padding; the scatter in
    ``apply_sparse_updates`` drops it)."""
    m_store, v_store = _sparse_rows_stores(
        shape, path, hparams, track_first_moment=track_first_moment,
        cleaning=cleaning, m_store=m_store, v_store=v_store)
    rule = T.scale_by_adam_rows_dp(
        b1=b1, b2=b2, eps=eps, m_store=m_store, v_store=v_store,
        axis_name=axis_name, error_feedback=error_feedback,
        dir_clip=dir_clip)
    return _with_lr(rule, lr)


def sparse_rows_adam_sharded(lr: Schedule, b1: float = 0.9,
                             b2: float = 0.999, eps: float = 1e-8, *,
                             shape: Tuple[int, int],
                             path: str = "sparse_rows",
                             shards: int,
                             shard_layout: str = "width",
                             shard_axis: str = "model",
                             dp_axis: Optional[str] = None,
                             hparams: SketchHParams = SketchHParams(),
                             track_first_moment: bool = True,
                             cleaning: Optional[CleaningSchedule] = None,
                             error_feedback: bool = False,
                             dir_clip: Optional[float] = 10.0,
                             m_store: Optional[AuxStore] = None,
                             v_store: Optional[AuxStore] = None) -> Transform:
    """``sparse_rows_adam_dp`` with the sketch state sharded over
    ``shard_axis`` into ``shards`` width slabs (DESIGN.md §17) — same
    store derivation and ``{"step", "m", "v", "residual"}`` layout, but
    ``update`` must run inside ``shard_map`` over the (dp × shard) mesh
    (``distributed.sharding.sharded_sparse_wrap``).  ``shard_layout``:
    'width' leaves the hashing untouched (state is byte-identical to the
    unsharded run; elastic re-placement across shard counts is free);
    'hash' routes whole ids to one owning shard (all of an id's depth
    rows shard-local) at the cost of re-hashing if the shard count ever
    changes.  Explicit stores are re-stamped with the requested sharding
    (``with_sharding``), so planner StoreTrees compose."""
    m_store, v_store = _sparse_rows_stores(
        shape, path, hparams, track_first_moment=track_first_moment,
        cleaning=cleaning, m_store=m_store, v_store=v_store)
    if v_store.spec is None or v_store.spec.shards != shards \
            or v_store.spec.layout != shard_layout:
        v_store = v_store.with_sharding(shards, shard_layout)
    if m_store is not None and (
            m_store.spec is None or m_store.spec.shards != shards
            or m_store.spec.layout != shard_layout):
        m_store = m_store.with_sharding(shards, shard_layout)
    backend = getattr(v_store, "backend", None) or hparams.backend
    rule = T.scale_by_adam_rows_sharded(
        b1=b1, b2=b2, eps=eps, m_store=m_store, v_store=v_store,
        shard_axis=shard_axis, dp_axis=dp_axis,
        error_feedback=error_feedback, dir_clip=dir_clip, backend=backend)
    return _with_lr(rule, lr)


def _sparse_rows_stores(shape: Tuple[int, int], path: str,
                        hparams: SketchHParams, *,
                        track_first_moment: bool,
                        cleaning: Optional[CleaningSchedule],
                        m_store: Optional[AuxStore],
                        v_store: Optional[AuxStore]
                        ) -> Tuple[Optional[AuxStore], AuxStore]:
    """The shared (m_store, v_store) derivation of the sparse-rows
    optimizers: ``hparams`` sizing unless explicit stores are given, with
    the cleaning-schedule consistency guards."""
    shape = tuple(int(s) for s in shape)
    if v_store is None:
        v_store = CountMinStore(spec=hparams.spec(path, shape, signed=False),
                                cleaning=cleaning, shape=shape)
    elif cleaning is not None:
        # an explicitly requested cleaning schedule must not be silently
        # dropped just because the store came from elsewhere (e.g. a plan
        # StoreTree, which carries no cleaning by default)
        if not isinstance(v_store, CountMinStore):
            raise ValueError(
                f"cleaning is a Count-Min hook (paper §4); the given "
                f"v_store is a {type(v_store).__name__} — drop cleaning= "
                f"or use a CountMinStore")
        if v_store.cleaning is None:
            v_store = dataclasses.replace(v_store, cleaning=cleaning)
        elif v_store.cleaning != cleaning:
            raise ValueError(
                f"conflicting cleaning schedules: v_store carries "
                f"{v_store.cleaning} but cleaning={cleaning} was also "
                f"passed — set exactly one")
    if m_store is None and track_first_moment:
        m_store = CountSketchStore(spec=hparams.spec(path, shape, signed=True),
                                   shape=shape)
    return (m_store if track_first_moment else None), v_store


def sparse_rows_stores(shape: Tuple[int, int], path: str = "sparse_rows",
                       hparams: SketchHParams = SketchHParams(), *,
                       track_first_moment: bool = True,
                       cleaning: Optional[CleaningSchedule] = None,
                       m_store: Optional[AuxStore] = None,
                       v_store: Optional[AuxStore] = None
                       ) -> Tuple[Optional[AuxStore], AuxStore]:
    """The EXACT (m_store, v_store) pair a ``sparse_rows_adam``(-dp) built
    with the same arguments binds — public so out-of-band consumers (the
    ``repro.obs`` table monitors, benchmarks) can read/``stats`` the same
    codecs the optimizer updates, instead of re-deriving specs by hand."""
    return _sparse_rows_stores(shape, path, hparams,
                               track_first_moment=track_first_moment,
                               cleaning=cleaning, m_store=m_store,
                               v_store=v_store)


def apply_sparse_updates(table: jnp.ndarray, updates) -> jnp.ndarray:
    """Apply ``sparse_rows_adam`` updates: scatter-ADD row updates at their
    ids (correct under every backend; see ``kernels.adam_rows``)."""
    return table.at[updates["ids"]].add(
        updates["rows"].astype(table.dtype))


def momentum_sparse_rows(spec: cs.SketchSpec, M: jnp.ndarray,
                         ids: jnp.ndarray, g: jnp.ndarray,
                         step: jnp.ndarray, *, lr: Schedule,
                         gamma: float = 0.9, strict_paper: bool = False):
    eta = _lr_at(lr, step)
    m_old = cs.query(spec, M, ids)
    delta = (gamma - 1.0) * m_old + g
    if strict_paper:
        M, m_new = cs.query_after_update(spec, M, ids, delta)
    else:
        M, m_new = cs.update_and_query(spec, M, ids, delta)
    return M, -eta * m_new


# ---------------------------------------------------------------------------
# Utilities
# ---------------------------------------------------------------------------

def linear_decay(base_lr: float, total_steps: int, floor: float = 0.0) -> Schedule:
    def sched(step):
        frac = jnp.clip(step.astype(jnp.float32) / float(total_steps), 0.0, 1.0)
        return base_lr * (1.0 - frac) + floor * frac
    return sched


def state_bytes(state) -> int:
    """Total bytes of optimizer auxiliary state (the paper's Tables 5/6):
    every array leaf counted shape × itemsize — dense buffers, sketch
    tensors, ``Rank1Moment`` factor pairs, the step scalar — with
    ``None`` leaves (β₁=0 layouts) contributing zero.  Exact on
    ``jax.eval_shape`` trees too; each store's own ``bytes()`` is the
    per-leaf predictor this total is regression-tested against
    (tests/test_stores.py)."""
    return stores_lib.tree_bytes(state)
