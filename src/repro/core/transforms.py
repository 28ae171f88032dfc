"""Composable gradient transforms: ``chain(clip_by_global_norm(0.1),
scale_by_adam(m_store=..., v_store=...), scale_by_lr(sched))``.

The update *rules* of the paper's optimizers (Algorithms 2–4), written
against the ``AuxStore`` codec protocol (``repro.core.stores``) so the
same rule runs over a dense buffer, a count-sketch, a count-min, or a
rank-1 factor pair — whatever the ``StoreTree`` resolves per leaf.

Contract (optax-shaped, self-contained): each transform is a
``Transform(init, update)`` pair; ``update(updates, state, params) ->
(updates, state)``.  ``scale_by_*`` rules emit the *ascent-preconditioned
direction* (no learning rate, no sign); ``scale_by_lr`` multiplies by
``-η(step)`` as the chain's final elementwise op.

Numerics: every op inside a rule is a verbatim port of the pre-refactor
``countsketch_*`` monoliths, so moment *states* evolve bit-identically to
them.  The one deliberate change is the final scale association — the
monoliths computed ``(-η·x)/denom``, the chain computes ``-η·(x/denom)``
— a ≤1-ulp difference on the emitted update (documented in DESIGN.md
§12; the legacy-parity reference in tests/legacy_reference.py pins the
chain association).

Execution: each rule consumes its stores through the fused
``AuxStore.update_read`` op (DESIGN.md §14) — one call per moment.
Stores with ``backend=None`` run the composed fallback under the
``dense_chunk`` scan (bit-identical legacy numerics); stores pinned to a
registry backend ('xla' | 'tiled' | 'interpret' | 'ref') take the whole
table through one fused kernel per moment instead.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.stores import AuxStore, DenseStore, Rank1Moment, StoreTree

Schedule = Union[float, Callable[[jnp.ndarray], jnp.ndarray]]


class Transform(NamedTuple):
    init: Callable[[Any], Any]
    update: Callable[..., Tuple[Any, Any]]  # (grads, state, params) -> (updates, state)


def _lr_at(lr: Schedule, step: jnp.ndarray) -> jnp.ndarray:
    return lr(step) if callable(lr) else jnp.asarray(lr, jnp.float32)


def _path_str(kp) -> str:
    parts = []
    for k in kp:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def tree_map_with_path(fn, tree, *rest):
    return jax.tree_util.tree_map_with_path(
        lambda kp, *leaves: fn(_path_str(kp), *leaves), tree, *rest)


def _flatten_moments(tree):
    """Flatten a moment tree keeping ``None``, ``Rank1Moment`` and
    ``QuantState`` as leaves (all are single store states, not
    containers)."""
    from repro.core.quantize import QuantState
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: x is None
        or isinstance(x, (Rank1Moment, QuantState)))
    return [leaf for _, leaf in flat], treedef


def _flatten_grads(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return [(_path_str(kp), leaf) for kp, leaf in flat], treedef


# ---------------------------------------------------------------------------
# Composition
# ---------------------------------------------------------------------------

def chain(*transforms) -> Transform:
    """Compose transforms left-to-right; state is the tuple of their
    states.  Anything with ``.init``/``.update`` composes (e.g. the
    ``clip_by_global_norm`` transform)."""

    def init(params=None):
        return tuple(t.init(params) for t in transforms)

    def update(updates, state, params=None):
        new_state = []
        for t, s in zip(transforms, state):
            updates, s = t.update(updates, s, params)
            new_state.append(s)
        return updates, tuple(new_state)

    return Transform(init, update)


def scale_by_lr(lr: Schedule) -> Transform:
    """Multiply float updates by ``-η(step)`` — the chain's terminal
    descent scale.  Integer leaves (e.g. the ``ids`` of a rows-gradient)
    and ``None`` leaves pass through untouched."""

    def init(params=None):
        return {"step": jnp.zeros((), jnp.int32)}

    def update(updates, state, params=None):
        step = state["step"] + 1
        eta = _lr_at(lr, step)

        def leaf(u):
            if u is None or not jnp.issubdtype(jnp.asarray(u).dtype,
                                               jnp.inexact):
                return u
            return -eta * u

        updates = jax.tree_util.tree_map(leaf, updates,
                                         is_leaf=lambda x: x is None)
        return updates, {"step": step}

    return Transform(init, update)


class ClipByGlobalNorm:
    """Scale updates so ‖updates‖₂ ≤ ``max_norm`` (the paper clips at
    0.1–1.0 in every experiment).  Usable both as a chain link
    (``chain(clip_by_global_norm(1.0), ...)``) and as a bare callable on
    a gradient tree (the pre-refactor calling convention)."""

    def __init__(self, max_norm: float):
        self.max_norm = float(max_norm)

    def __call__(self, grads):
        leaves = jax.tree_util.tree_leaves(grads)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                          for g in leaves))
        scale = jnp.minimum(1.0, self.max_norm / (gn + 1e-12))
        return jax.tree_util.tree_map(lambda g: g * scale.astype(g.dtype),
                                      grads)

    def init(self, params=None):
        return {}

    def update(self, updates, state, params=None):
        return self(updates), state


def clip_by_global_norm(max_norm: float) -> ClipByGlobalNorm:
    return ClipByGlobalNorm(max_norm)


# ---------------------------------------------------------------------------
# Shared leaf plumbing (ports of the monolith helpers — op-identical)
# ---------------------------------------------------------------------------

def _pick_chunk(n: int, target: int) -> int:
    """Largest divisor of n that is ≤ target (rows are vocab-padded to a
    multiple of 128, so a 128-granular divisor always exists)."""
    if target <= 0 or n <= target:
        return n
    for c in range(target, 0, -1):
        if n % c == 0:
            return c
    return n


def _row_active(g):
    """1.0 for rows with any non-zero gradient, else 0.0: the row-active
    mask every sketched path applies."""
    return jnp.any(g != 0, axis=-1, keepdims=True).astype(jnp.float32)


def _sketched_rows_scan(g, carry0, step_chunk, chunk: int, extra=None):
    """Run ``step_chunk(carry, ids, g_chunk, [extra_chunk]) -> (carry, u)``
    over row chunks of the dense gradient ``g`` (n, d) in one
    ``lax.scan``; ``extra`` is an optional second (n, d) array chunked
    alongside (CS-V mode passes dense m̂ rows through)."""
    n, d = g.shape
    chunk = _pick_chunk(n, chunk)
    nc = n // chunk
    ids = jnp.arange(n, dtype=jnp.int32).reshape(nc, chunk)
    xs = (ids, g.reshape(nc, chunk, d))
    if extra is not None:
        xs = xs + (extra.reshape(nc, chunk, d),)

    def body(carry, xs_):
        return step_chunk(carry, *xs_)

    carry, u = jax.lax.scan(body, carry0, xs)
    return carry, u.reshape(n, d)


def _fused(store: Optional[AuxStore]) -> bool:
    """True when the store's ``update_read`` runs as one fused kernel
    (a registry backend is pinned) — the transform then hands it the
    whole table in one call instead of chunk-scanning (DESIGN.md §14)."""
    return store is not None and getattr(store, "backend", None) is not None


# ---------------------------------------------------------------------------
# scale_by_momentum (paper Alg. 2)
# ---------------------------------------------------------------------------

def scale_by_momentum(gamma: float = 0.9, *,
                      stores: Optional[StoreTree] = None,
                      m_store: Optional[AuxStore] = None,
                      where=None,
                      dense_chunk: int = 8192,
                      strict_paper: bool = False) -> Transform:
    """Polyak momentum ``m ← γm + g``; emits ``m`` (the direction).  The
    per-leaf m store is the ``StoreTree``'s m slot: ``DenseStore`` runs
    the closed form, ``CountSketchStore`` the paper's linear form
    ``Δ = (γ−1)·m̂ + g`` over the sketch."""
    if stores is None:
        stores = StoreTree.select(m=m_store if m_store is not None
                                  else DenseStore(), v=None, where=where,
                                  default_v=None)

    def _m(path, leaf):
        m, _ = stores.resolve(path, tuple(leaf.shape), leaf.dtype)
        if m is None or m.kind not in ("dense", "sketch"):
            raise ValueError(f"scale_by_momentum needs a dense or signed "
                             f"count-sketch m store at {path!r}, got "
                             f"{None if m is None else m.kind}")
        return m

    def init(params):
        return {"step": jnp.zeros((), jnp.int32),
                "m": tree_map_with_path(
                    lambda p, leaf: _m(p, leaf).init(), params)}

    def update(grads, state, params=None):
        step = state["step"] + 1

        def leaf(path, g, M):
            ms = _m(path, g)
            if ms.kind == "dense":
                m_new, _ = ms.update_read(M, g, gamma, scale=1.0)
                return m_new, m_new
            if _fused(ms) and not strict_paper:
                # one fused kernel over the whole table (DESIGN.md §14)
                act = _row_active(g)
                M_out, m_est = ms.update_read(M, g, gamma, scale=1.0,
                                              mask=act, step=step)
                return M_out, act * m_est
            if dense_chunk and not strict_paper:
                def chunk_step(carry, ids, gc):
                    act = _row_active(gc)
                    carry, m_est = ms.update_read(
                        carry, gc, gamma, scale=1.0, rows=ids,
                        mask=act, read_state=M, step=step)
                    return carry, act * m_est
                return _sketched_rows_scan(g, M, chunk_step, dense_chunk)
            act = _row_active(g)
            M_out, m_est = ms.update_read(M, g, gamma, scale=1.0, mask=act,
                                          strict=strict_paper, step=step)
            return M_out, act * m_est

        pairs = tree_map_with_path(leaf, grads, state["m"])
        is2 = lambda x: isinstance(x, tuple)
        m = jax.tree_util.tree_map(lambda t: t[0], pairs, is_leaf=is2)
        updates = jax.tree_util.tree_map(lambda t: t[1], pairs, is_leaf=is2)
        return updates, {"step": step, "m": m}

    return Transform(init, update)


# ---------------------------------------------------------------------------
# scale_by_adagrad (paper Alg. 3)
# ---------------------------------------------------------------------------

def scale_by_adagrad(eps: float = 1e-10, *,
                     stores: Optional[StoreTree] = None,
                     v_store: Optional[AuxStore] = None,
                     where=None,
                     dense_chunk: int = 8192,
                     strict_paper: bool = False) -> Transform:
    """Adagrad ``v ← v + g²``; emits ``g / (√v + ε)``.  The cumulative
    squared gradient lives in the ``StoreTree``'s v slot (``DenseStore``
    or ``CountMinStore`` — the paper's Alg. 3)."""
    if stores is None:
        stores = StoreTree.select(v=v_store if v_store is not None
                                  else DenseStore(), m=None, where=where,
                                  default_m=None)

    def _v(path, leaf):
        _, v = stores.resolve(path, tuple(leaf.shape), leaf.dtype)
        if v is None or v.kind not in ("dense", "countmin"):
            raise ValueError(f"scale_by_adagrad needs a dense or count-min "
                             f"v store at {path!r}, got "
                             f"{None if v is None else v.kind}")
        return v

    def init(params):
        return {"step": jnp.zeros((), jnp.int32),
                "v": tree_map_with_path(
                    lambda p, leaf: _v(p, leaf).init(), params)}

    def update(grads, state, params=None):
        step = state["step"] + 1

        def leaf(path, g, V):
            vs = _v(path, g)
            if vs.kind == "dense":
                v_new, _ = vs.update_read(V, g * g, 1.0, scale=1.0)
                return v_new, g / (jnp.sqrt(v_new) + eps)
            V_in = vs.clean(V, step)
            if _fused(vs) and not strict_paper:
                # one fused kernel over the whole table (DESIGN.md §14)
                V_out, v_est = vs.update_read(V_in, g * g, 1.0,
                                              scale=1.0, step=step)
                v_new = jnp.maximum(v_est, 0.0)
                return V_out, g / (jnp.sqrt(v_new) + eps)
            if dense_chunk and not strict_paper:
                def chunk_step(carry, ids, gc):
                    carry, v_est = vs.update_read(carry, gc * gc, 1.0,
                                                  scale=1.0, rows=ids,
                                                  read_state=V_in, step=step)
                    v_new = jnp.maximum(v_est, 0.0)
                    return carry, gc / (jnp.sqrt(v_new) + eps)
                return _sketched_rows_scan(g, V_in, chunk_step, dense_chunk)
            V_out, v_est = vs.update_read(V_in, g * g, 1.0, scale=1.0,
                                          strict=strict_paper, step=step)
            v_new = jnp.maximum(v_est, 0.0)
            return V_out, g / (jnp.sqrt(v_new) + eps)

        pairs = tree_map_with_path(leaf, grads, state["v"])
        is2 = lambda x: isinstance(x, tuple)
        v = jax.tree_util.tree_map(lambda t: t[0], pairs, is_leaf=is2)
        updates = jax.tree_util.tree_map(lambda t: t[1], pairs, is_leaf=is2)
        return updates, {"step": step, "v": v}

    return Transform(init, update)


# ---------------------------------------------------------------------------
# scale_by_adam (paper Alg. 4) — the store-parameterized core
# ---------------------------------------------------------------------------

_UNSET = object()


def scale_by_adam(b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8, *,
                  stores: Optional[StoreTree] = None,
                  m_store: Any = _UNSET, v_store: Any = _UNSET,
                  where=None,
                  dense_chunk: int = 8192,
                  strict_paper: bool = False) -> Transform:
    """Adam whose moments live wherever the ``StoreTree`` says: per leaf,
    the 1st moment in a ``DenseStore``, a ``CountSketchStore`` (signed,
    median) or nowhere (``None`` ⇒ β₁=0 for that leaf), and the 2nd
    moment in a ``DenseStore``, ``CountMinStore`` (min query, optional
    cleaning), ``CountSketchStore`` or ``Rank1Store`` (LR-NMF-V).  Emits
    the bias-corrected preconditioned direction ``m̂ / (√v̂ + ε)``.

    ``m_store``/``v_store`` + ``where`` is sugar for a two-level
    ``StoreTree``: selected leaves get those stores, the rest stay dense
    (pass ``m_store=None`` for the β₁=0 layout).  ``dense_chunk`` and
    ``strict_paper`` are the execution knobs of the old ``SketchHParams``,
    unchanged in meaning.  Rows whose gradient is entirely zero get no
    update and no sketch write: the paper's per-item algorithm touches
    only active features."""
    if stores is None:
        stores = StoreTree.select(
            m=DenseStore() if m_store is _UNSET else m_store,
            v=DenseStore() if v_store is _UNSET else v_store,
            where=where)

    def _mv(path, leaf):
        ms, vs = stores.resolve(path, tuple(leaf.shape), leaf.dtype)
        if vs is None:
            raise ValueError(f"scale_by_adam needs a v store at {path!r}")
        if ms is not None and ms.kind not in ("dense", "sketch"):
            raise ValueError(f"unsupported m store kind {ms.kind!r} at "
                             f"{path!r} (dense | sketch | None)")
        if vs.kind == "dense" and ms is not None and ms.kind == "sketch":
            raise ValueError(f"sketched m over dense v at {path!r} is not "
                             f"a paper layout (sketch the 2nd moment too)")
        return ms, vs

    def init(params):
        def m_leaf(path, p):
            ms, _ = _mv(path, p)
            return ms.init() if ms is not None else None

        def v_leaf(path, p):
            _, vs = _mv(path, p)
            return vs.init()

        return {"step": jnp.zeros((), jnp.int32),
                "m": tree_map_with_path(m_leaf, params),
                "v": tree_map_with_path(v_leaf, params)}

    def update(grads, state, params=None):
        step = state["step"] + 1
        t = step.astype(jnp.float32)
        bc1 = 1.0 - b1 ** t
        bc2 = 1.0 - b2 ** t

        def leaf(path, g, M, V):
            ms, vs = _mv(path, g)

            if vs.kind == "rank1":
                # LR-NMF-V leaf: rank-1 2nd moment (fused decay + mean-
                # accumulate + read via the codec), dense 1st — numerics
                # identical to lowrank.nmf_rank1_adam.
                g2 = jnp.square(g.astype(jnp.float32))
                V_out, vhat = vs.update_read(V, g2, b2, scale=(1.0 - b2))
                if ms is not None:
                    M_out, m_new = ms.update_read(M, g, b1)
                    mhat = m_new / bc1
                else:
                    M_out, mhat = None, g
                upd = mhat / (jnp.sqrt(jnp.maximum(vhat / bc2, 0.0)) + eps)
                return M_out, V_out, upd

            if vs.kind == "dense":
                # fully dense leaf.  The v delta is pre-scaled
                # ``((1−β₂)·g)·g`` — the monoliths' association (the
                # sketched paths scale ``g²`` inside ``ema_delta``).
                if ms is None:
                    mhat, M_out = g, None
                else:
                    M_out, m_new = ms.update_read(M, g, b1)
                    mhat = m_new / bc1
                v_new, _ = vs.update_read(V, (1.0 - b2) * g * g, b2,
                                          scale=1.0)
                return M_out, v_new, mhat / (jnp.sqrt(v_new / bc2) + eps)

            # sketched 2nd moment (count-min, or signed count-sketch)
            sketched_m = ms is not None and ms.kind == "sketch"
            V_in = vs.clean(V, step)

            # dense 1st moment alongside a sketched 2nd (paper's CS-V mode)
            if ms is not None and not sketched_m:
                M_out, m_dense = ms.update_read(M, g, b1)
                mhat_rows = m_dense / bc1
            else:
                M_out, mhat_rows = None, None

            fused = (not strict_paper and _fused(vs)
                     and (not sketched_m or _fused(ms)))
            if fused:
                # one fused kernel per moment over the whole table —
                # the single-pass hot path (DESIGN.md §14).  The §4
                # cleaning hook fired above on V_in, exactly as on the
                # composed paths.
                act = _row_active(g)
                if sketched_m:
                    M_out, m_est = ms.update_read(M, g, b1, mask=act,
                                                  step=step)
                    mhat = m_est / bc1
                elif ms is not None:
                    mhat = mhat_rows
                else:
                    mhat = g
                V_out, v_est = vs.update_read(V_in, g * g, b2, mask=act,
                                              step=step)
                vh = jnp.maximum(v_est, 0.0) / bc2
                return M_out, V_out, act * mhat / (jnp.sqrt(vh) + eps)

            if dense_chunk and not strict_paper:
                # composed chunked scan: one ``update_read`` per moment
                # per chunk, O(depth·chunk·d) temps.  Estimates close
                # over the PRE-step sketches via ``read_state``
                # (canonical batch semantics).
                def chunk_step(carry, ids, gc, *mh_c):
                    act = _row_active(gc)
                    if sketched_m:
                        carry["M"], m_est = ms.update_read(
                            carry["M"], gc, b1, rows=ids, mask=act,
                            read_state=M, step=step)
                        mh = m_est / bc1
                    elif ms is not None:
                        mh = mh_c[0]
                    else:
                        mh = gc
                    carry["V"], v_est = vs.update_read(
                        carry["V"], gc * gc, b2, rows=ids, mask=act,
                        read_state=V_in, step=step)
                    vh = jnp.maximum(v_est, 0.0) / bc2
                    return carry, act * mh / (jnp.sqrt(vh) + eps)

                carry0 = {"V": V_in}
                if sketched_m:
                    carry0["M"] = M
                carry, upd = _sketched_rows_scan(
                    g, carry0, chunk_step, dense_chunk, extra=mhat_rows)
                if sketched_m:
                    M_out = carry["M"]
                return M_out, carry["V"], upd

            # reference unchunked path (also the strict-paper 3-pass mode)
            act = _row_active(g)
            if sketched_m:
                M_out, m_est = ms.update_read(M, g, b1, mask=act,
                                              strict=strict_paper, step=step)
                mhat = m_est / bc1
            elif ms is not None:
                mhat = mhat_rows
            else:
                mhat = g
            V_out, v_est = vs.update_read(V_in, g * g, b2, mask=act,
                                          strict=strict_paper, step=step)
            v_new = jnp.maximum(v_est, 0.0)
            upd = act * mhat / (jnp.sqrt(v_new / bc2) + eps)
            return M_out, V_out, upd

        flat_g, gdef = _flatten_grads(grads)
        flat_m, mdef = _flatten_moments(state["m"])
        flat_v, vdef = _flatten_moments(state["v"])
        m_out, v_out, dirs = [], [], []
        for (path, g), M, V in zip(flat_g, flat_m, flat_v):
            Mo, Vo, u = leaf(path, g, M, V)
            m_out.append(Mo)
            v_out.append(Vo)
            dirs.append(u)
        unf = jax.tree_util.tree_unflatten
        return unf(gdef, dirs), {"step": step,
                                 "m": unf(mdef, m_out),
                                 "v": unf(vdef, v_out)}

    return Transform(init, update)


def scale_by_adam_rows_dp(b1: float = 0.9, b2: float = 0.999,
                          eps: float = 1e-8, *,
                          m_store: Optional[AuxStore],
                          v_store: AuxStore,
                          axis_name: str = "data",
                          error_feedback: bool = False,
                          dir_clip: Optional[float] = 10.0) -> Transform:
    """Data-parallel ``scale_by_adam_rows``: the same one-table (ids, rows)
    contract, but ``update`` must run inside ``shard_map`` (or
    ``vmap(axis_name=...)``) over ``axis_name`` with the sketch state
    replicated and the (ids, rows) batch sharded.

    Each replica sketches its LOCAL gradient shard; the collectives move
    the (depth, width, dim) sketches and the int32 id shards — never the
    (k, d) gradient rows (``repro.distributed.sketched_reduce.dp_adam_rows``
    is the body; DESIGN.md §13).  ``error_feedback=True`` adds the
    MicroAdam-style residual sketch that accumulates the 2nd-moment
    cross-replica term instead of dropping it.

    Emits ``{"ids": global_unique_ids, "rows": direction}`` with the
    direction unscaled — compose with ``scale_by_lr`` and apply via
    ``apply_sparse_updates`` (the fill-id padding is out of range, so the
    scatter drops it).  ``dir_clip``: the per-coordinate trust clamp on
    the emitted direction (sketch-noise guard — see ``dp_adam_rows``;
    None disables)."""
    for name, store, kinds in (("m_store", m_store, ("sketch",)),
                               ("v_store", v_store, ("countmin", "sketch"))):
        if store is None:
            continue
        if store.kind not in kinds or store.spec is None:
            raise ValueError(f"{name} must be a bound (explicit-spec) "
                             f"{'/'.join(kinds)} store, got {store!r}")
    spec_m = m_store.spec if m_store is not None else None
    spec_v = v_store.spec

    def init(params=None):
        from repro.distributed import sketched_reduce as sr
        return {"step": jnp.zeros((), jnp.int32),
                "m": m_store.init() if m_store is not None else None,
                "v": v_store.init(),
                "residual": (sr.init_feedback(spec_v)
                             if error_feedback else None)}

    def update(grads, state, params=None):
        from repro.distributed import sketched_reduce as sr
        ids, rows = grads["ids"], grads["rows"]
        step = state["step"] + 1
        V_in = v_store.clean(state["v"], step)
        out = sr.dp_adam_rows(
            spec_m, spec_v, state["m"], V_in, ids, rows, step,
            axis_name=axis_name, b1=b1, b2=b2, eps=eps,
            residual=state["residual"], dir_clip=dir_clip)
        return ({"ids": out.uids, "rows": out.rows},
                {"step": step, "m": out.M, "v": out.V,
                 "residual": out.residual})

    return Transform(init, update)


def scale_by_adam_rows_sharded(b1: float = 0.9, b2: float = 0.999,
                               eps: float = 1e-8, *,
                               m_store: Optional[AuxStore],
                               v_store: AuxStore,
                               shard_axis: str = "model",
                               dp_axis: Optional[str] = None,
                               error_feedback: bool = False,
                               dir_clip: Optional[float] = 10.0,
                               backend: Optional[str] = None) -> Transform:
    """``scale_by_adam_rows_dp`` with the sketch state SHARDED over
    ``shard_axis`` (DESIGN.md §17): the stores' specs must declare
    ``shards > 1`` (``AuxStore.with_sharding`` / the planner's
    ``sketch_shards``), and ``update`` must run inside ``shard_map`` over
    the (dp × shard) mesh — ``distributed.sharding.sharded_sparse_wrap``
    is the canonical wrapper — where every rank-3 state leaf the
    transform sees is this device's (depth, local_width, dim) slab.

    ``init`` still returns FULL (depth, width, dim) arrays: sharding is
    placement-only (the jit in_shardings put each slab on its shard),
    which is what makes width-layout elastic restore across shard counts
    a pure re-placement.  ``dp_axis=None`` is the shard-only mesh (no
    data parallelism); with both axes the body composes PR 4's DP psums
    with the shard-axis routing collective
    (``sketched_reduce.sharded_adam_rows``)."""
    for name, store, kinds in (("m_store", m_store, ("sketch",)),
                               ("v_store", v_store, ("countmin", "sketch"))):
        if store is None:
            continue
        if store.kind not in kinds or store.spec is None:
            raise ValueError(f"{name} must be a bound (explicit-spec) "
                             f"{'/'.join(kinds)} store, got {store!r}")
        if store.spec.shards < 2:
            raise ValueError(f"{name} is not sharded (spec.shards == "
                             f"{store.spec.shards}); use "
                             f"scale_by_adam_rows_dp for replicated state "
                             f"or with_sharding() the store")
    spec_m = m_store.spec if m_store is not None else None
    spec_v = v_store.spec
    if spec_m is not None and (spec_m.shards != spec_v.shards
                               or spec_m.layout != spec_v.layout):
        raise ValueError(f"m/v stores disagree on the shard layout: "
                         f"{spec_m.shards}×{spec_m.layout!r} vs "
                         f"{spec_v.shards}×{spec_v.layout!r}")

    def init(params=None):
        from repro.distributed import sketched_reduce as sr
        return {"step": jnp.zeros((), jnp.int32),
                "m": m_store.init() if m_store is not None else None,
                "v": v_store.init(),
                "residual": (sr.init_feedback(spec_v)
                             if error_feedback else None)}

    def update(grads, state, params=None):
        from repro.distributed import sketched_reduce as sr
        ids, rows = grads["ids"], grads["rows"]
        step = state["step"] + 1
        V_in = v_store.clean(state["v"], step)   # α-multiply: slab-safe
        out = sr.sharded_adam_rows(
            spec_m, spec_v, state["m"], V_in, ids, rows, step,
            shard_axis=shard_axis, dp_axis=dp_axis, b1=b1, b2=b2, eps=eps,
            residual=state["residual"], dir_clip=dir_clip, backend=backend)
        return ({"ids": out.uids, "rows": out.rows},
                {"step": step, "m": out.M, "v": out.V,
                 "residual": out.residual})

    return Transform(init, update)


def scale_by_rmsprop(b2: float = 0.999, eps: float = 1e-8, *,
                     stores: Optional[StoreTree] = None,
                     v_store: Any = _UNSET, where=None,
                     dense_chunk: int = 8192,
                     strict_paper: bool = False) -> Transform:
    """The β₁=0 rule of Theorem 5.1 (Count-Min Adam without the 1st
    moment): ``scale_by_adam`` with every m slot forced to ``None`` —
    the layout the paper runs for the 49.5M-class Amazon task."""
    if stores is None:
        stores = StoreTree.select(
            m=None, v=DenseStore() if v_store is _UNSET else v_store,
            where=where, default_m=None)
    return scale_by_adam(b1=0.0, b2=b2, eps=eps,
                         stores=stores.without_first_moment(),
                         dense_chunk=dense_chunk,
                         strict_paper=strict_paper)


# ---------------------------------------------------------------------------
# scale_by_adam over a rows-indexed store view (the sparse fast path)
# ---------------------------------------------------------------------------

def scale_by_adam_rows(b1: float = 0.9, b2: float = 0.999,
                       eps: float = 1e-8, *,
                       m_store: Optional[AuxStore],
                       v_store: AuxStore,
                       backend: Optional[str] = None,
                       dir_clip: Optional[float] = None) -> Transform:
    """``scale_by_adam`` for ONE table fed ``{"ids": (k,), "rows": (k, d)}``
    gradients — the sampled-softmax / extreme-classification regime where
    work scales with touched rows.

    ``m_store`` (``CountSketchStore`` or None for β₁=0) and ``v_store``
    (``CountMinStore``, cleaning hook honored) must be bound (explicit
    ``spec``); the step routes their specs through the kernel-backend
    registry (``repro.kernels``: 'ref' | 'xla' | 'tiled' | 'interpret',
    None/'auto' = per-host best; any other name raises ``KeyError``),
    which handles duplicate ids.  Emits ``{"ids", "rows": direction}``
    with the direction *unscaled* — compose with ``scale_by_lr`` (which
    leaves the integer ``ids`` leaf untouched) and apply via
    ``apply_sparse_updates``.

    ``dir_clip``: the per-coordinate trust clamp of the DP path
    (``sketched_reduce.dp_adam_rows``), for the same reason: the numerator
    m̂ is a signed-median sketch estimate, so a row whose buckets hold a
    heavier row's first moment, divided by its own small count-min
    second moment, can get a direction of 1e5 and more where exact Adam
    stays within a few units.  None (the default) leaves it unclamped."""
    for name, store, kinds in (("m_store", m_store, ("sketch",)),
                               ("v_store", v_store, ("countmin", "sketch"))):
        if store is None:
            continue
        if store.kind not in kinds or store.spec is None:
            raise ValueError(f"{name} must be a bound (explicit-spec) "
                             f"{'/'.join(kinds)} store, got {store!r}")
    spec_m = m_store.spec if m_store is not None else None
    spec_v = v_store.spec

    def init(params=None):
        return {"step": jnp.zeros((), jnp.int32),
                "m": m_store.init() if m_store is not None else None,
                "v": v_store.init()}

    def update(grads, state, params=None):
        from repro import kernels  # deferred: kernels import jax-level deps
        ids, rows = grads["ids"], grads["rows"]
        step = state["step"] + 1
        V_in = v_store.clean(state["v"], step)
        # lr=-1.0 makes the kernels emit the raw ascent direction (an
        # exact ±1 multiply), leaving the descent scale to scale_by_lr.
        M, V, direction = kernels.adam_rows(
            spec_m, spec_v, state["m"], V_in, ids, rows, step,
            lr=-1.0, b1=b1, b2=b2, eps=eps, backend=backend)
        if dir_clip is not None:
            direction = jnp.clip(direction, -dir_clip, dir_clip)
        return ({"ids": ids, "rows": direction},
                {"step": step, "m": M, "v": V})

    return Transform(init, update)
