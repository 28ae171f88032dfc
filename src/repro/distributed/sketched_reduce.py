"""Sketched gradient reduction (beyond-paper; DESIGN.md §4, §13).

The count-sketch is linear, so for a data-parallel embedding/softmax
gradient the cross-replica reduction commutes with sketching:

    sketch(psum(g)) == psum(sketch(g))            (exact, not approximate)

The CS optimizer only ever *consumes* the gradient through sketch
updates (`Δ_M = (1-β₁)(g - m_old)` splits into a sketched `g` term and a
local `m_old` term) — so for the 1st moment the dense (k, d) gradient
never needs to cross pods: each replica inserts its LOCAL rows into a
zero sketch and the all-reduce moves ``depth·width·d`` elements instead
of ``k·d`` — a 5–20× byte cut at the paper's compressions on the
dominant embedding-gradient collective (``traffic_ratio`` below, in
bytes, ids payload included).

The 2nd moment needs ``psum(g)²`` which does NOT commute with the sum of
per-replica squares; ``reduce_moments`` sums per-replica squares and —
when given a ``residual`` — adds the MicroAdam-style error-feedback
correction: each replica's exact share of the cross-replica term,
``g_r·(Σg − g_r)``, estimated through the already-reduced 1st-moment
sketch, is banked in a residual sketch and injected into the reduced
2nd-moment increment whenever the injection keeps it non-negative.

``dp_adam_rows`` is the full per-replica CS-Adam update built on these
collectives — the body that ``train.steps.make_sparse_embedding_step
(dp_axis=...)`` runs inside ``shard_map``.  Property tests in
tests/test_distributed.py assert the exactness of the linear part;
tests/test_distributed_dp.py runs the 8-device parity grid.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import sketch as cs
from repro.kernels import dedup as dd


def local_sketch(spec: cs.SketchSpec, ids: jnp.ndarray,
                 rows: jnp.ndarray) -> jnp.ndarray:
    """Insert this replica's (ids, rows) gradient contribution into a
    fresh sketch — the object that gets all-reduced instead of (k, d)."""
    return cs.update(spec, cs.init(spec), ids, rows)


def reduce_gradient_sketch(spec: cs.SketchSpec, ids: jnp.ndarray,
                           rows: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """psum of per-replica sketches == sketch of the psum'd gradient.
    Call inside shard_map/pmap over ``axis_name``."""
    with jax.named_scope("obs.collective"):
        return jax.lax.psum(local_sketch(spec, ids, rows), axis_name)


# ---------------------------------------------------------------------------
# Traffic accounting (bytes, not element counts)
# ---------------------------------------------------------------------------

def dense_reduce_bytes(n_rows: int, dim: int, *,
                       grad_dtype=jnp.float32,
                       ids_dtype=jnp.int32,
                       with_ids: bool = True) -> int:
    """Bytes the DENSE data-parallel path must move per replica to combine
    an (ids, rows) gradient batch of ``n_rows`` touched rows: the row
    payload plus — unless the gradient is already table-dense — the ids
    (and their offsets, same int payload) that address it."""
    payload = n_rows * dim * jnp.dtype(grad_dtype).itemsize
    if with_ids:
        payload += n_rows * jnp.dtype(ids_dtype).itemsize
    return payload


def sketched_reduce_bytes(*specs: Optional[cs.SketchSpec]) -> int:
    """Bytes the sketched path all-reduces: the sum of every live sketch's
    ``nbytes()`` (1st-moment sketch, 2nd-moment sketch, optional
    error-feedback cross-term sketch)."""
    return sum(s.nbytes() for s in specs if s is not None)


def traffic_ratio(spec: cs.SketchSpec, n_rows: int, *,
                  grad_dtype=jnp.float32,
                  with_ids: bool = True,
                  extra_specs: Tuple[Optional[cs.SketchSpec], ...] = ()
                  ) -> float:
    """Dense all-reduce bytes / sketched all-reduce bytes (BYTES, dtype-
    aware — a bf16 sketch really is half an f32 one — and the dense
    path's ids payload is charged to it).  ``extra_specs``: further
    sketches riding the same collective (e.g. the 2nd-moment sketch)."""
    dense = dense_reduce_bytes(n_rows, spec.dim, grad_dtype=grad_dtype,
                               with_ids=with_ids)
    return dense / sketched_reduce_bytes(spec, *extra_specs)


def sharded_reduce_bytes(*specs: Optional[cs.SketchSpec]) -> int:
    """Bytes the SHARDED gradient-sketch psum moves per device: one width
    slab per live sketch (1/shards of the replicated payload — the whole
    point of DESIGN.md §17's layout)."""
    return sum(s.shard_nbytes() for s in specs if s is not None)


def routing_bytes(n_rows: int, *specs: Optional[cs.SketchSpec]) -> int:
    """Bytes of the shard-axis ROUTING collective per device per step: the
    psum that assembles each query group's (depth, k, dim) contribution
    rows across shards (``sharded_query``).  Charged once per live sketch
    per query group — the sharded layout's price for shard-local state."""
    return sum(s.depth * n_rows * s.dim * jnp.dtype(s.dtype).itemsize
               for s in specs if s is not None)


# ---------------------------------------------------------------------------
# 2nd-moment reduction with MicroAdam-style error feedback
# ---------------------------------------------------------------------------

def init_feedback(spec_v: cs.SketchSpec) -> jnp.ndarray:
    """Zero error-feedback residual, in the 2nd-moment sketch's geometry."""
    return cs.init(spec_v)


def _inject_feedback(g_v: jnp.ndarray, residual: jnp.ndarray,
                     cross_sketch: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Error feedback: bank this step's cross-term sketch into the
    residual, inject as much as keeps the (non-negative, count-min)
    2nd-moment increment ≥ 0 per bucket, carry the rest forward."""
    total = residual + cross_sketch
    inject = jnp.maximum(total, -g_v)
    return g_v + inject, total - inject


def reduce_moments(spec_m: cs.SketchSpec, spec_v: cs.SketchSpec,
                   ids: jnp.ndarray, rows: jnp.ndarray, axis_name: str, *,
                   residual: Optional[jnp.ndarray] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray]]:
    """(G_m, G_v, residual'): all-reduced sketches of g and (approximately)
    g², plus the updated error-feedback residual.

    G_m is exact (linearity).  G_v sums per-replica squares — it misses
    the cross-replica terms of (Σ_r g_r)²; with R replicas of i.i.d.
    noise this underestimates v by ≈ the inter-replica covariance, the
    same bias accepted by local-accumulation optimizers.

    Pass ``residual`` (from ``init_feedback``) to opt into the error-
    feedback correction: each replica's share of the cross term,
    ``g_r·(Σg − g_r)``, with Σg estimated by querying the exact reduced
    1st-moment sketch, is sketched, reduced, banked, and injected (the
    injection is clamped so the count-min increment stays non-negative;
    the unapplied remainder carries to the next step — MicroAdam,
    Modoranu et al. 2024).  The share is clipped at ``−g_r²`` so every
    row's NET contribution (square + correction) to its buckets stays
    ≥ 0 — without the clip, median-noise in the Σg estimate can park
    negative mass in buckets shared with other rows, zero their min
    query, and blow up the downstream ``m̂/(√v̂+ε)`` direction (a
    conservative under-correction when gradients anti-align across
    replicas).  With ``residual=None`` the bias is accepted and ``None``
    is returned in its slot."""
    g_m = reduce_gradient_sketch(spec_m, ids, rows, axis_name)
    with jax.named_scope("obs.collective"):
        g_v = jax.lax.psum(
            cs.update(spec_v, cs.init(spec_v), ids, jnp.square(rows)),
            axis_name)
    if residual is None:
        return g_m, g_v, None
    g_sum = cs.query(spec_m, g_m, ids)            # ≈ Σ_r g_r at local ids
    cross = jnp.maximum(rows * (g_sum - rows),    # this replica's share,
                        -jnp.square(rows))        # net-non-negative per row
    with jax.named_scope("obs.collective"):
        g_c = jax.lax.psum(
            cs.update(spec_v, cs.init(spec_v), ids, cross), axis_name)
    g_v, residual = _inject_feedback(g_v, residual, g_c)
    return g_m, g_v, residual


# ---------------------------------------------------------------------------
# Global id set (the only non-sketch collective the DP step needs)
# ---------------------------------------------------------------------------

def global_unique_ids(local_ids: jnp.ndarray, axis_name: str, *,
                      fill_id: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """All-gather each replica's (locally deduplicated, ``fill_id``-padded)
    id list and deduplicate across replicas.

    Returns ``(uids, mask)`` of length ``R·k``: sorted global unique ids
    then ``fill_id`` padding, and a float mask of live slots.  This is the
    cheap collective — ids are int32, 1/dim'th of the row payload — that
    lets every replica apply the (replicated) table update exactly once
    per touched row."""
    gathered = jax.lax.all_gather(local_ids, axis_name)     # (R, k)
    flat = gathered.reshape(-1)
    k = flat.shape[0]
    sorted_ids = jnp.sort(flat)
    live = sorted_ids != fill_id
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]]) & live
    seg = jnp.cumsum(is_start.astype(jnp.int32)) - 1
    # dead (padding) positions scatter out of range so they cannot clobber
    # the last live slot (their seg still points at it)
    uids = jnp.full((k,), fill_id, jnp.int32).at[
        jnp.where(live, seg, k)].set(sorted_ids, mode="drop")
    n_unique = jnp.sum(is_start.astype(jnp.int32))
    mask = (jnp.arange(k) < n_unique).astype(jnp.float32)
    return uids, mask


# ---------------------------------------------------------------------------
# The full per-replica DP CS-Adam update (shard_map body)
# ---------------------------------------------------------------------------

class DpAdamResult(NamedTuple):
    M: Optional[jnp.ndarray]      # updated 1st-moment sketch (replicated)
    V: jnp.ndarray                # updated 2nd-moment sketch (replicated)
    residual: Optional[jnp.ndarray]   # updated error-feedback residual
    uids: jnp.ndarray             # (R·k,) global unique ids (+ fill padding)
    rows: jnp.ndarray             # (R·k, d) ascent direction per unique id
    mask: jnp.ndarray             # (R·k,) 1.0 for live slots


def dp_adam_rows(spec_m: Optional[cs.SketchSpec], spec_v: cs.SketchSpec,
                 M: Optional[jnp.ndarray], V: jnp.ndarray,
                 ids: jnp.ndarray, rows: jnp.ndarray, step: jnp.ndarray, *,
                 axis_name: str, b1: float = 0.9, b2: float = 0.999,
                 eps: float = 1e-8,
                 residual: Optional[jnp.ndarray] = None,
                 fill_id: Optional[int] = None,
                 dir_clip: Optional[float] = 10.0) -> DpAdamResult:
    """One data-parallel CS-Adam step over a replicated (n, d) table whose
    gradient arrives as per-replica ``(ids, rows)`` shards.  Call inside
    ``shard_map``/``vmap(axis_name=...)`` over ``axis_name`` with sketch
    state replicated and (ids, rows) sharded.

    The collectives move sketches, never gradient rows:

      * ``psum`` of the per-replica 1st-moment gradient sketches — EXACT
        by linearity, so the M state update below is the single-device
        update on the concatenated batch (bit-identical under dyadic
        hyperparameters, ≤ float-associativity noise otherwise);
      * ``psum`` of the per-replica squared-row sketches (+ the optional
        error-feedback cross-term sketch — see ``reduce_moments``);
      * ``all_gather`` of the int32 id shards — the only per-row payload.

    When ``spec_m`` is None (β₁=0, Theorem 5.1), ``spec_v``'s signed twin
    is used as the transient gradient sketch for the numerator estimate.

    Emits the UNSCALED ascent direction at the global unique ids (compose
    with ``scale_by_lr``; apply with ``table.at[uids].add(...)`` — the
    ``fill_id`` padding defaults to an out-of-range id that scatter mode
    'drop' ignores).

    ``dir_clip``: per-coordinate trust clamp on the emitted direction.
    Both moments here are sketch queries — a signed-median numerator over
    a count-min denominator (as on one device:
    ``transforms.scale_by_adam_rows``) — so per-id estimator mismatch
    can exceed exact Adam's ~1-bounded |m̂/√v̂| ratio and, fed back
    through the loss, diverge.  Exact Adam never legitimately exceeds a
    few units per coordinate; the clamp (default 10) only ever removes
    sketch noise.  ``None`` disables."""
    track_m = spec_m is not None
    # replace(), not a field-list constructor: the g sketch must inherit
    # EVERY layout field of spec_v — dropping shards/layout here would
    # hash the gradient differently from a hash-layout v store
    spec_g = spec_m if track_m else dataclasses.replace(spec_v, signed=True)
    if fill_id is None:
        fill_id = jnp.iinfo(jnp.int32).max  # out of range for any table
    t = step.astype(jnp.float32)
    bc1 = 1.0 - b1 ** t
    bc2 = 1.0 - b2 ** t

    # 1. local dedup: duplicate ids inside a replica batch are occurrences
    #    of the same dense-gradient row; segment-sum them first so the
    #    intra-replica cross terms of g² are exact (kernels/dedup.py).
    batch = dd.dedup_rows(ids, rows, fill_id=fill_id)
    lids, lrows = batch.unique_ids, batch.rows

    # 2. sketch collectives (the traffic win) + error feedback — the
    #    shared reduction, so the −g² share clip and injection clamp
    #    live in exactly one place.
    G_g, G_v, residual = reduce_moments(spec_g, spec_v, lids, lrows,
                                        axis_name, residual=residual)

    # 3. the id collective: every replica learns the global touched set.
    uids, mask = global_unique_ids(lids, axis_name, fill_id=fill_id)
    col = mask[:, None]

    # 4. replicated state update — the single-device xla-backend update
    #    with the summed-gradient scatter replaced by its sketch identity:
    #    sketch((1-β₁)·Σg at uids) == (1-β₁)·psum(local sketches).
    if track_m:
        m_old = cs.query(spec_m, M, uids) * col
        M_out = cs.update(spec_m, M + (1.0 - b1) * G_g, uids,
                          -(1.0 - b1) * m_old)
        ghat = cs.query(spec_g, G_g, uids) * col      # ≈ Σg at uids
        mhat = (m_old + (1.0 - b1) * (ghat - m_old)) / bc1
    else:
        M_out = None
        ghat = cs.query(spec_g, G_g, uids) * col
        mhat = ghat
    v_old = cs.query(spec_v, V, uids) * col
    g2hat = cs.query(spec_v, G_v, uids) * col         # ≈ Σg² (+ feedback)
    V_out = cs.update(spec_v, V + (1.0 - b2) * G_v, uids,
                      -(1.0 - b2) * v_old)
    vhat = jnp.maximum(v_old + (1.0 - b2) * (g2hat - v_old), 0.0) / bc2
    direction = col * mhat / (jnp.sqrt(vhat) + eps)
    if dir_clip is not None:
        direction = jnp.clip(direction, -dir_clip, dir_clip)
    return DpAdamResult(M=M_out, V=V_out, residual=residual,
                        uids=uids, rows=direction, mask=mask)


# ---------------------------------------------------------------------------
# Model-parallel sketches: the sharded-slab step (DESIGN.md §17)
# ---------------------------------------------------------------------------

def sharded_query(spec: cs.SketchSpec, slab: jnp.ndarray, ids: jnp.ndarray,
                  shard_axis: str, *,
                  backend: Optional[str] = None) -> jnp.ndarray:
    """Exact ``cs.query`` against a width-sharded sketch: each shard
    gathers its slab's (unsigned) contribution rows, a psum over
    ``shard_axis`` assembles them — every (depth-row, id) cell lives on
    exactly one shard, so the sum is assembly, not approximation — and
    ``finish_query`` applies signs + median / min.  The routing
    collective moves ``depth·k·dim`` elements (``routing_bytes``)."""
    from repro import kernels
    shard = jax.lax.axis_index(shard_axis)
    part = kernels.gather_slab(spec, slab, ids, shard, backend=backend)
    with jax.named_scope("obs.route"):
        part = jax.lax.psum(part, shard_axis)
    return cs.finish_query(spec, part, ids)


def sharded_adam_rows(spec_m: Optional[cs.SketchSpec], spec_v: cs.SketchSpec,
                      M: Optional[jnp.ndarray], V: jnp.ndarray,
                      ids: jnp.ndarray, rows: jnp.ndarray,
                      step: jnp.ndarray, *, shard_axis: str,
                      dp_axis: Optional[str] = None,
                      b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                      residual: Optional[jnp.ndarray] = None,
                      fill_id: Optional[int] = None,
                      dir_clip: Optional[float] = 10.0,
                      backend: Optional[str] = None) -> DpAdamResult:
    """``dp_adam_rows`` with the sketch state SHARDED over ``shard_axis``:
    ``M``/``V``/``residual`` are this device's (depth, local_width, dim)
    slabs, and the specs carry ``shards``/``layout`` (DESIGN.md §17).
    Call inside ``shard_map`` over a (dp × shard) mesh with the batch
    sharded on ``dp_axis`` (replicated across ``shard_axis``) and the
    slabs sharded on ``shard_axis`` (replicated across ``dp_axis``).

    Per-device collective traffic, vs PR 4's replicated step:

      * gradient-sketch psum over ``dp_axis`` moves one SLAB per sketch —
        a ``shards``× cut (``sharded_reduce_bytes``);
      * the new shard-axis routing psum assembles the query groups'
        (depth, k, dim) contribution rows (``routing_bytes``) — ids that
        hash off-slab contribute zeros, which is exactly the locality-
        aware all-to-all in psum clothing (under the 'hash' layout a
        whole id's rows come from ONE shard; under 'width' from up to
        ``depth``);
      * the id all_gather over ``dp_axis`` is unchanged.

    Exactness is inherited: slab updates concatenate to the full-width
    update and assembled queries equal full-width queries bit-for-bit
    (tests/test_sharded.py), so with ``dp_axis`` set this step matches
    ``dp_adam_rows`` — and the single-device step — under dyadic β
    exactly like PR 4.  ``dp_axis=None`` runs shard-only (one replica):
    no dp collectives, the local dedup alone defines the touched set.
    """
    from repro import kernels
    track_m = spec_m is not None
    spec_g = spec_m if track_m else dataclasses.replace(spec_v, signed=True)
    if fill_id is None:
        fill_id = jnp.iinfo(jnp.int32).max
    t = step.astype(jnp.float32)
    bc1 = 1.0 - b1 ** t
    bc2 = 1.0 - b2 ** t
    shard = jax.lax.axis_index(shard_axis)

    # 1. local dedup (identical to the replicated step).
    batch = dd.dedup_rows(ids, rows, fill_id=fill_id)
    lids, lrows = batch.unique_ids, batch.rows

    # 2. gradient sketches as SLABS: each (dp, shard) device sketches its
    #    local rows into its own slab; the dp psum then moves slab bytes,
    #    not full sketches.  Exact: update(S) == concat_s(update_slab).
    G_g = kernels.update_slab(spec_g, cs.init_slab(spec_g), lids, lrows,
                              shard, backend=backend)
    G_v = kernels.update_slab(spec_v, cs.init_slab(spec_v), lids,
                              jnp.square(lrows), shard, backend=backend)
    if dp_axis is not None:
        with jax.named_scope("obs.collective"):
            G_g, G_v = jax.lax.psum((G_g, G_v), dp_axis)

    # error feedback (MicroAdam, as in reduce_moments) on slabs: the
    # cross-term share needs Σg at the local ids — one routing query —
    # and the banking/injection arithmetic is per-bucket, so it applies
    # to slabs unchanged.
    if residual is not None:
        g_sum = sharded_query(spec_g, G_g, lids, shard_axis,
                              backend=backend)
        cross = jnp.maximum(lrows * (g_sum - lrows), -jnp.square(lrows))
        G_c = kernels.update_slab(spec_v, cs.init_slab(spec_v), lids,
                                  cross, shard, backend=backend)
        if dp_axis is not None:
            with jax.named_scope("obs.collective"):
                G_c = jax.lax.psum(G_c, dp_axis)
        G_v, residual = _inject_feedback(G_v, residual, G_c)

    # 3. the global touched set (dp collective; shard-only runs skip it).
    if dp_axis is not None:
        uids, mask = global_unique_ids(lids, dp_axis, fill_id=fill_id)
    else:
        uids, mask = lids, (lids != fill_id).astype(jnp.float32)
    col = mask[:, None]

    # 4. state update.  All four query groups share one routing psum (the
    #    contributions stack into a single collective); the scatter
    #    halves are shard-local — zero collective traffic.
    parts = [kernels.gather_slab(spec_g, G_g, uids, shard, backend=backend),
             kernels.gather_slab(spec_v, V, uids, shard, backend=backend),
             kernels.gather_slab(spec_v, G_v, uids, shard, backend=backend)]
    if track_m:
        parts.append(kernels.gather_slab(spec_m, M, uids, shard,
                                         backend=backend))
    with jax.named_scope("obs.route"):
        parts = jax.lax.psum(tuple(parts), shard_axis)
    ghat = cs.finish_query(spec_g, parts[0], uids) * col
    v_old = cs.finish_query(spec_v, parts[1], uids) * col
    g2hat = cs.finish_query(spec_v, parts[2], uids) * col
    if track_m:
        m_old = cs.finish_query(spec_m, parts[3], uids) * col
        M_out = kernels.update_slab(spec_m, M + (1.0 - b1) * G_g, uids,
                                    -(1.0 - b1) * m_old, shard,
                                    backend=backend)
        mhat = (m_old + (1.0 - b1) * (ghat - m_old)) / bc1
    else:
        M_out = None
        mhat = ghat
    V_out = kernels.update_slab(spec_v, V + (1.0 - b2) * G_v, uids,
                                -(1.0 - b2) * v_old, shard, backend=backend)
    vhat = jnp.maximum(v_old + (1.0 - b2) * (g2hat - v_old), 0.0) / bc2
    direction = col * mhat / (jnp.sqrt(vhat) + eps)
    if dir_clip is not None:
        direction = jnp.clip(direction, -dir_clip, dir_clip)
    return DpAdamResult(M=M_out, V=V_out, residual=residual,
                        uids=uids, rows=direction, mask=mask)
