"""The Count-Sketch Tensor (paper §2, §4) as a pure-functional JAX structure.

State is a single array ``S`` of shape ``(depth, width, dim)``:

  * ``depth`` rows of independent hash functions (paper: 3–5 suffice),
  * ``width`` buckets (``width ≪ n`` — the compression),
  * ``dim``   — the *uncompressed*, contiguous trailing axis of the
    auxiliary variable ("structured sparsity", paper Fig. 3).  On TPU this
    axis is tiled to the 128-lane dimension, so all random access happens
    on the bucket axis only.

Two estimators:
  * signed  (Count-Sketch):   UPDATE adds ``s_j(i)·Δ``; QUERY is the
    median over depth of ``s_j(i)·S[j, h_j(i)]``  — for signed variables
    (momentum, Adam 1st moment).
  * unsigned (Count-Min):     UPDATE adds ``Δ`` (no signs); QUERY is the
    min over depth — for non-negative variables (Adagrad / Adam 2nd
    moment).

Canonical batch semantics
-------------------------
The paper's per-item algorithms QUERY, UPDATE, then QUERY again.  For a
single item the second query equals ``first_query + Δ`` *exactly* (the
median/min shifts uniformly).  We therefore define the batched step as

    est_old = query(S, ids)
    S'      = update(S, ids, Δ)
    est_new = est_old + Δ          # paper-equivalent, one less sketch pass

which is bit-identical to the paper for collision-free batches and saves a
full gather pass (see EXPERIMENTS.md §Perf — this is the first of the
beyond-paper optimizations; the strict 3-pass variant is kept as
``query_after_update`` for the fidelity tests).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import quantize as qz
from repro.core.hashing import HashFamily
from repro.core.quantize import QuantState


@dataclasses.dataclass(frozen=True)
class SketchSpec:
    """Static description of a sketch tensor (hashable; safe as a jit const).

    ``shards``/``layout`` declare how the width axis partitions over a
    mesh axis (DESIGN.md §17).  They change NOTHING about the logical
    state shape — ``init`` still allocates the full ``(depth, width,
    dim)`` tensor and checkpoints stay whole-array — only how buckets are
    assigned ('hash' constrains all of an id's rows to one shard's slab;
    'width' leaves hashing untouched) and which slab primitives below
    operate shard-locally.
    """

    depth: int
    width: int
    dim: int
    signed: bool = True          # True: Count-Sketch (median); False: Count-Min (min)
    seed: int = 0
    dtype: jnp.dtype = jnp.float32   # cell storage dtype (f32 | bf16 | int8)
    identity: bool = False       # test mode: exact table when width >= n
    shards: int = 1              # width-axis partitions (1 = unsharded)
    layout: str = "width"        # 'width' | 'hash' (see HashFamily)
    scale_block: int = qz.SCALE_BLOCK  # int8: buckets per f32 scale

    def __post_init__(self):
        if self.layout not in ("width", "hash"):
            raise ValueError(f"unknown shard layout {self.layout!r} "
                             f"(expected 'width' or 'hash')")
        if self.shards < 1 or self.width % self.shards != 0:
            raise ValueError(f"sketch width {self.width} must divide into "
                             f"{self.shards} shards")
        qz.cell_dtype_name(self.dtype)    # reject unsupported cell dtypes
        if self.quantized and self.shards > 1:
            raise ValueError(
                "int8 sketch cells do not compose with model-parallel "
                "sharding yet: a width slab would split scale blocks "
                "across devices — use bfloat16 or float32 cells, or "
                "shards=1 (DESIGN.md §18)")
        if self.scale_block < 1:
            raise ValueError(f"scale_block must be >= 1, "
                             f"got {self.scale_block}")

    @property
    def quantized(self) -> bool:
        """True when cells are int8 (state is a ``QuantState``)."""
        return jnp.dtype(self.dtype) == jnp.int8

    @property
    def cell_dtype_name(self) -> str:
        return qz.cell_dtype_name(self.dtype)

    @property
    def family(self) -> HashFamily:
        return HashFamily(seed=self.seed, depth=self.depth, width=self.width,
                          identity=self.identity, shards=self.shards,
                          layout=self.layout)

    @property
    def shape(self) -> Tuple[int, int, int]:
        return (self.depth, self.width, self.dim)

    @property
    def local_width(self) -> int:
        """Width of one shard's slab."""
        return self.width // self.shards

    @property
    def slab_shape(self) -> Tuple[int, int, int]:
        """Shape of one shard's slab: (depth, width/shards, dim)."""
        return (self.depth, self.local_width, self.dim)

    def nbytes(self) -> int:
        """Exact byte footprint of ``init(self)`` — dtype-aware (a bf16
        sketch is half an fp32 one; an int8 sketch adds its f32 scale
        blocks), the ground truth the memory-budget planner's accounting
        (``repro.plan.accounting``) must agree with."""
        cells = self.depth * self.width * self.dim \
            * jnp.dtype(self.dtype).itemsize
        if self.quantized:
            return cells + self.depth * qz.n_blocks(self.width,
                                                    self.scale_block) * 4
        return cells

    def shard_nbytes(self) -> int:
        """Per-device byte footprint when sharded: one slab."""
        return self.nbytes() // self.shards

    def fold(self) -> "SketchSpec":
        # family.fold() owns the divisibility checks (even width, halved
        # width still divides into shards)
        self.family.fold()
        return dataclasses.replace(self, width=self.width // 2)


def for_param(shape: Tuple[int, ...], *, compression: float = 5.0,
              depth: int = 3, signed: bool = True, seed: int = 0,
              dtype=jnp.float32, width_multiple: int = 256,
              identity: bool = False) -> SketchSpec:
    """Spec for a (n, d) auxiliary variable compressed ``compression`` ×.

    Width is rounded up to ``width_multiple`` so the bucket axis divides the
    mesh axes it may be sharded over (and the fold stays exact).
    """
    if len(shape) != 2:
        raise ValueError(f"sketched params must be rank-2 (rows, dim), got {shape}")
    n, d = shape
    if identity:
        # exact-table test mode: every row gets its own bucket
        w = -(-n // width_multiple) * width_multiple
        return SketchSpec(depth=depth, width=w, dim=d, signed=signed,
                          seed=seed, dtype=dtype, identity=True)
    w = max(int(n / (compression * depth)), 1)
    w = -(-w // width_multiple) * width_multiple  # ceil to multiple
    w = min(w, max(n, width_multiple))
    return SketchSpec(depth=depth, width=w, dim=d, signed=signed, seed=seed,
                      dtype=dtype, identity=identity)


def for_budget(shape: Tuple[int, ...], nbytes: int, *, depth: int = 3,
               signed: bool = True, seed: int = 0, dtype=jnp.float32,
               width_multiple: int = 256,
               identity: bool = False) -> SketchSpec:
    """Inverse of ``for_param``: the widest spec whose ``nbytes()`` fits a
    byte budget.  Width is floored to ``width_multiple`` (the result never
    exceeds the budget) and capped at the identity point — ≥ n buckets is
    already an exact table, more would be pure waste.

    Raises ``ValueError`` when the budget cannot fund even one
    ``width_multiple`` stripe of buckets; callers wanting a fallback
    should catch it and keep the leaf dense (or rank-1)."""
    if len(shape) != 2:
        raise ValueError(f"sketched params must be rank-2 (rows, dim), got {shape}")
    n, d = shape
    itemsize = jnp.dtype(dtype).itemsize
    w = int(nbytes) // (depth * d * itemsize)
    w = (w // width_multiple) * width_multiple      # floor to multiple
    if w < width_multiple:
        need = depth * width_multiple * d * itemsize
        raise ValueError(
            f"budget {int(nbytes)} B funds no {width_multiple}-bucket stripe "
            f"for shape {shape} at depth {depth} (needs ≥ {need} B)")
    w = min(w, -(-n // width_multiple) * width_multiple)
    spec = SketchSpec(depth=depth, width=w, dim=d, signed=signed, seed=seed,
                      dtype=jnp.dtype(dtype), identity=identity)
    # int8 carries f32 scale blocks on top of the cells; shave stripes
    # until the EXACT footprint (nbytes()) fits the budget again
    while spec.nbytes() > int(nbytes):
        w -= width_multiple
        if w < width_multiple:
            raise ValueError(
                f"budget {int(nbytes)} B funds no {width_multiple}-bucket "
                f"stripe for shape {shape} at depth {depth} once the "
                f"int8 scale blocks are accounted")
        spec = dataclasses.replace(spec, width=w)
    return spec


def init(spec: SketchSpec):
    """Zero state: a plain array for f32/bf16 cells, a ``QuantState``
    (int8 cells + f32 block scales) for quantized specs."""
    if spec.quantized:
        return QuantState(
            cells=jnp.zeros(spec.shape, dtype=jnp.int8),
            scales=jnp.zeros((spec.depth,
                              qz.n_blocks(spec.width, spec.scale_block)),
                             dtype=jnp.float32))
    return jnp.zeros(spec.shape, dtype=spec.dtype)


def sr_seed_or_default(spec: SketchSpec, sr_seed):
    """The stochastic-rounding seed low-precision writes use: the caller's
    per-step seed when given, else the spec's pinned step-0 stream."""
    return sr_seed if sr_seed is not None else qz.step_seed(spec.seed)


def median_rows(rows) -> jnp.ndarray:
    """Median over a LIST of per-depth rows, elementwise min/max only —
    the single source of the estimator identity shared by the reference
    query, the fused XLA update_read, and the Pallas kernels (bit-identity
    across them depends on these exact forms; a sort does not lower in a
    TPU kernel).

    depth 3 keeps its historical form a+b+c−max−min; other depths run an
    odd-even transposition network and take the middle element (odd) or
    ``jnp.median``'s midpoint ``(lo + hi) · 0.5`` (even)."""
    n = len(rows)
    if n == 1:
        return rows[0]
    if n == 3:
        hi = jnp.maximum(jnp.maximum(rows[0], rows[1]), rows[2])
        lo = jnp.minimum(jnp.minimum(rows[0], rows[1]), rows[2])
        return rows[0] + rows[1] + rows[2] - hi - lo
    vals = list(rows)
    for rnd in range(n):
        for i in range(rnd % 2, n - 1, 2):
            vals[i], vals[i + 1] = (jnp.minimum(vals[i], vals[i + 1]),
                                    jnp.maximum(vals[i], vals[i + 1]))
    if n % 2:
        return vals[n // 2]
    return (vals[n // 2 - 1] + vals[n // 2]) * 0.5


def _median_depth(vals: jnp.ndarray) -> jnp.ndarray:
    """Median over axis 0 of a stacked (depth, ...) array."""
    return median_rows([vals[i] for i in range(vals.shape[0])])


def query(spec: SketchSpec, S, ids: jnp.ndarray) -> jnp.ndarray:
    """QUERY (paper Alg. 1): estimate rows ``ids`` -> (k, dim).

    Low-precision cells dequantize in the gather (int8 cells multiply
    their block's scale; bf16 widens) and the estimator runs in f32 —
    the f32 path is bit-identical to the historical query."""
    fam = spec.family
    b = fam.bucket(ids)                       # (depth, k)
    if spec.quantized:
        cells = jax.vmap(lambda Sj, bj: Sj[bj])(S.cells, b)  # (d, k, dim)
        sc = qz.bucket_scales(S.scales, b, spec.scale_block)  # (d, k)
        gathered = cells.astype(jnp.float32) * sc[..., None]
        if not spec.signed:
            # Unsigned estimates floor at the quantizer's resolution:
            # a cell only resolves values to ±scale/2, so a read below
            # that is indistinguishable from zero — and an adaptive
            # denominator (Adam's sqrt(v)) built on it would collapse
            # for rows whose block absmax dwarfs their own moment.
            # Never-written blocks keep scale 0, so exact zeros survive.
            gathered = jnp.maximum(gathered, 0.5 * sc[..., None])
    else:
        gathered = jax.vmap(lambda Sj, bj: Sj[bj])(S, b)     # (depth, k, dim)
        if gathered.dtype != jnp.float32:
            gathered = gathered.astype(jnp.float32)
    if spec.signed:
        s = fam.sign(ids)                     # (depth, k)
        gathered = gathered * s[..., None].astype(gathered.dtype)
        return _median_depth(gathered)
    return jnp.min(gathered, axis=0)


def _scatter_upd(spec: SketchSpec, ids: jnp.ndarray, delta: jnp.ndarray,
                 dtype) -> jnp.ndarray:
    """(depth, k, dim) per-row scatter payload: signed or broadcast."""
    if spec.signed:
        s = spec.family.sign(ids)                         # (depth, k)
        return s[..., None].astype(dtype) * delta[None].astype(dtype)
    return jnp.broadcast_to(delta[None].astype(dtype),
                            (spec.depth,) + delta.shape)


def _update_quant(spec: SketchSpec, S: QuantState, ids: jnp.ndarray,
                  delta: jnp.ndarray, sr_seed) -> QuantState:
    """int8 UPDATE: dequantize, scatter-add in f32, stochastically
    re-round the touched cells.  Scales grow monotonically (never shrink
    between cleanings), so untouched cells in unchanged blocks keep their
    exact int8 value — no re-rounding random walk.  When a block's scale
    grows, the whole block re-rounds once at the new scale."""
    d, w, dim = spec.shape
    fam = spec.family
    b = fam.bucket(ids)
    upd = _scatter_upd(spec, ids, delta, jnp.float32)
    est = qz.dequantize(S, spec.scale_block)
    new = jax.vmap(lambda Ej, bj, uj: Ej.at[bj].add(uj))(est, b, upd)
    touched = jax.vmap(
        lambda bj: jnp.zeros((w,), jnp.bool_).at[bj].set(True))(b)
    scales = qz.grown_scales(S.scales, new, spec.scale_block)
    grew = qz.expand_scales(scales > S.scales, w, spec.scale_block)
    need = (touched | grew)[:, :, None]
    s = qz.expand_scales(scales, w, spec.scale_block)[:, :, None]
    safe = jnp.where(s > 0, s, jnp.float32(1.0))
    bits = qz.cell_bits(sr_seed, qz._lin_index(spec.shape))
    q = qz.sr_int8(new / safe, bits)
    q = jnp.where(s > 0, q, jnp.int8(0))
    return QuantState(cells=jnp.where(need, q, S.cells), scales=scales)


def update(spec: SketchSpec, S, ids: jnp.ndarray, delta: jnp.ndarray,
           sr_seed=None):
    """UPDATE (paper Alg. 1): add ``delta`` (k, dim) at rows ``ids``.

    Batch-colliding ids accumulate correctly (scatter-add).  Writes to
    low-precision cells go through stochastic rounding keyed by
    ``sr_seed`` (``quantize.step_seed`` — pass the per-step seed on the
    hot path; None pins the step-0 stream).  bf16 accumulates in f32 and
    re-rounds; untouched bf16 cells are exactly preserved (truncation of
    a representable value cannot carry)."""
    if spec.quantized:
        return _update_quant(spec, S, ids, delta,
                             sr_seed_or_default(spec, sr_seed))
    fam = spec.family
    b = fam.bucket(ids)                                   # (depth, k)
    if S.dtype == jnp.bfloat16:
        upd = _scatter_upd(spec, ids, delta, jnp.float32)
        inc = jax.vmap(
            lambda bj, uj: jnp.zeros((spec.width, spec.dim),
                                     jnp.float32).at[bj].add(uj))(b, upd)
        bits = qz.cell_bits(sr_seed_or_default(spec, sr_seed),
                            qz._lin_index(spec.shape))
        return qz.sr_bfloat16(S.astype(jnp.float32) + inc, bits)
    upd = _scatter_upd(spec, ids, delta, S.dtype)
    return jax.vmap(lambda Sj, bj, uj: Sj.at[bj].add(uj))(S, b, upd)


def update_and_query(spec: SketchSpec, S, ids: jnp.ndarray,
                     delta: jnp.ndarray, sr_seed=None):
    """Canonical batched step: returns (S', est_new).  See module docstring."""
    est_old = query(spec, S, ids)
    S = update(spec, S, ids, delta, sr_seed=sr_seed)
    return S, est_old + delta


def query_after_update(spec: SketchSpec, S, ids: jnp.ndarray,
                       delta: jnp.ndarray, sr_seed=None):
    """Strict paper semantics (3 sketch passes): update then re-gather."""
    S = update(spec, S, ids, delta, sr_seed=sr_seed)
    return S, query(spec, S, ids)


def decay(S, alpha):
    """Cleaning heuristic (paper §4): multiply the sketch by ``alpha``.

    int8 state decays EXACTLY by folding ``alpha`` into the block scales
    — an O(depth · n_blocks) multiply that never touches a cell, which
    is what makes async cleaning's pending-decay fold free."""
    if isinstance(S, QuantState):
        return QuantState(cells=S.cells,
                          scales=S.scales * jnp.float32(alpha))
    return S * jnp.asarray(alpha, dtype=S.dtype)


# ---------------------------------------------------------------------------
# Shard-slab primitives (DESIGN.md §17) — the model-parallel decomposition
# of UPDATE/QUERY.  A shard holds the contiguous width slab
# ``S[:, shard·lw : (shard+1)·lw]`` (lw = width/shards); these ops use the
# FULL-width hash family and mask to the slab, so
#
#     update(S)            == concat_s(update_slab(slab_s))       (exact)
#     gather of query(S)   == Σ_s gather_slab(slab_s)             (exact)
#
# — each (depth-row, id) cell is owned by exactly one shard, making the
# sum an assembly, not an approximation.  The distributed layer
# (``repro.distributed.sketched_reduce``) runs these inside ``shard_map``
# with a psum over the shard axis as the routing collective; they are
# equally valid single-device (loop over shards), which is how the parity
# tests pin exactness.  Under the 'hash' layout every row of an owned id
# is in-slab, so the owner's update_slab IS the whole update for that id.
# ---------------------------------------------------------------------------

def init_slab(spec: SketchSpec) -> jnp.ndarray:
    """Zero slab for one shard: (depth, width/shards, dim)."""
    return jnp.zeros(spec.slab_shape, dtype=spec.dtype)


def slab_of(spec: SketchSpec, S: jnp.ndarray, shard: int) -> jnp.ndarray:
    """Shard ``shard``'s width slab of a full sketch tensor."""
    lw = spec.local_width
    return S[:, shard * lw:(shard + 1) * lw]


def _slab_buckets(spec: SketchSpec, ids: jnp.ndarray, shard):
    """(local buckets clamped to [0, lw], ownership mask) for one shard.

    Out-of-slab entries get local bucket ``lw`` — one past the slab — so
    scatter mode 'drop' discards them and gathers clamp+mask them."""
    lw = spec.local_width
    b = spec.family.bucket(ids)                    # (depth, k) full width
    local = b - jnp.asarray(shard, jnp.int32) * lw
    own = (local >= 0) & (local < lw)
    return jnp.where(own, local, lw), own


def update_slab(spec: SketchSpec, slab: jnp.ndarray, ids: jnp.ndarray,
                delta: jnp.ndarray, shard, sr_seed=None) -> jnp.ndarray:
    """Shard-local UPDATE: scatter-add the slab-owned portion of ``delta``
    at ``ids``; rows hashing outside the slab are dropped (they belong to
    another shard).  ``shard`` may be a traced scalar (lax.axis_index).
    bf16 slabs accumulate in f32 and stochastically re-round (untouched
    cells preserved exactly — representable truncation cannot carry)."""
    local, _ = _slab_buckets(spec, ids, shard)
    work = jnp.float32 if slab.dtype == jnp.bfloat16 else slab.dtype
    if spec.signed:
        upd = spec.family.sign(ids)[..., None].astype(work) \
            * delta[None].astype(work)
    else:
        upd = jnp.broadcast_to(delta[None].astype(work),
                               (spec.depth,) + delta.shape)
    if slab.dtype == jnp.bfloat16:
        inc = jax.vmap(
            lambda bj, uj: jnp.zeros((spec.local_width, spec.dim),
                                     jnp.float32)
            .at[bj].add(uj, mode="drop"))(local, upd)
        bits = qz.cell_bits(sr_seed_or_default(spec, sr_seed),
                            qz._lin_index(slab.shape))
        return qz.sr_bfloat16(slab.astype(jnp.float32) + inc, bits)
    return jax.vmap(lambda Sj, bj, uj: Sj.at[bj].add(uj, mode="drop"))(
        slab, local, upd)


def gather_slab(spec: SketchSpec, slab: jnp.ndarray, ids: jnp.ndarray,
                shard) -> jnp.ndarray:
    """Shard-local half of QUERY: this slab's additive contribution to the
    pre-estimator gathered values — (depth, k, dim), zero for cells owned
    elsewhere.  Sum over shards (psum over the shard axis), then finish
    with ``finish_query``."""
    local, own = _slab_buckets(spec, ids, shard)
    lw = spec.local_width
    gathered = jax.vmap(lambda Sj, bj: Sj[jnp.minimum(bj, lw - 1)])(
        slab, local)
    return jnp.where(own[..., None], gathered,
                     jnp.zeros((), dtype=slab.dtype))


def finish_query(spec: SketchSpec, assembled: jnp.ndarray,
                 ids: jnp.ndarray) -> jnp.ndarray:
    """QUERY's estimator half on assembled (depth, k, dim) gathered values
    (the Σ over shards of ``gather_slab``, or a plain full-width gather):
    signs + median for Count-Sketch, min over depth for Count-Min.  Uses
    the same ``median_rows`` form as ``query`` — bit-identical results."""
    if spec.signed:
        s = spec.family.sign(ids)
        assembled = assembled * s[..., None].astype(assembled.dtype)
        return _median_depth(assembled)
    return jnp.min(assembled, axis=0)


def ema_delta(est_old: jnp.ndarray, x: jnp.ndarray, beta: float,
              scale: float) -> jnp.ndarray:
    """The sketched linear-EMA increment: the Δ that moves a row's content
    from ``est_old`` to ``β·est_old + scale·x``.

    The THREE algebraic forms below are value-equal but round differently;
    which one runs is pinned so the fused kernels and the composed
    fallback stay bit-identical to the historical transforms:

      * Adam moments (``scale == 1-β``):   ``scale·(x − est_old)``
      * Adagrad (``β == 1``):              ``scale·x``        (no est term)
      * momentum (``scale == 1``, β=γ):    ``(β−1)·est_old + x``

    ``beta``/``scale`` are static Python floats — the branch resolves at
    trace time.
    """
    sx = x if scale == 1.0 else scale * x
    if scale == 1.0 - beta:
        return scale * (x - est_old)
    if beta == 1.0:
        return sx
    return (beta - 1.0) * est_old + sx


def fold(spec: SketchSpec, S: jnp.ndarray) -> Tuple[SketchSpec, jnp.ndarray]:
    """Hokusai fold (paper §5): halve the width, adding the upper half into
    the lower.  Exact w.r.t. the ``h mod (w/2)`` re-bucketing because
    ``(x mod w) mod (w/2) == x mod (w/2)`` for even ``w``.  Used for elastic
    memory scaling (shrink optimizer state mid-training without reset).

    Shard layouts fold differently (DESIGN.md §17): the 'hash' layout's
    buckets are ``owner·lw + (h mod lw)``, so the exact fold halves each
    shard's LOCAL range — upper half-slab into lower half-slab, never
    crossing shard boundaries (a sharded deployment folds with zero
    collective traffic).  The 'width' layout (and identity mode, whose
    buckets ignore the layout) keeps the classic whole-width fold; under
    sharding its column pairs sit ``shards/2`` slabs apart, which the
    full-array restore path handles for free."""
    if spec.width % 2 != 0:
        raise ValueError("fold requires an even width")
    if spec.quantized:
        # dequantize-add-requantize: the folded content gets fresh absmax
        # scales and one stochastic re-round (seeded from the spec — the
        # fold is a one-shot op, not a per-step write)
        half = spec.width // 2
        dense = qz.dequantize(S, spec.scale_block)
        folded = dense[:, :half] + dense[:, half:]
        return spec.fold(), qz.quantize(folded, qz.step_seed(spec.seed),
                                        scale_block=spec.scale_block)
    # bf16 folds exactly in f32 and re-rounds once stochastically
    dense = S.astype(jnp.float32) if S.dtype == jnp.bfloat16 else S
    if spec.layout == "hash" and spec.shards > 1 and not spec.identity:
        lw = spec.local_width
        if lw % 2 != 0:
            raise ValueError(f"hash-layout fold needs an even local width, "
                             f"got {lw}")
        ranged = dense.reshape(spec.depth, spec.shards, lw, spec.dim)
        folded = ranged[:, :, :lw // 2] + ranged[:, :, lw // 2:]
        folded = folded.reshape(spec.depth, spec.width // 2, spec.dim)
    else:
        half = spec.width // 2
        folded = dense[:, :half] + dense[:, half:]
    if S.dtype == jnp.bfloat16:
        bits = qz.cell_bits(qz.step_seed(spec.seed),
                            qz._lin_index(folded.shape))
        return spec.fold(), qz.sr_bfloat16(folded, bits)
    return spec.fold(), folded


# ---------------------------------------------------------------------------
# Dense-row helpers (the whole table 0..n-1 at once) — used when the train
# step hands the optimizer a dense gradient for a sketched parameter.
# ---------------------------------------------------------------------------

def query_dense(spec: SketchSpec, S: jnp.ndarray, n: int) -> jnp.ndarray:
    return query(spec, S, jnp.arange(n, dtype=jnp.int32))


def update_dense(spec: SketchSpec, S: jnp.ndarray, delta: jnp.ndarray) -> jnp.ndarray:
    n = delta.shape[0]
    return update(spec, S, jnp.arange(n, dtype=jnp.int32), delta)


def update_and_query_dense(spec: SketchSpec, S: jnp.ndarray,
                           delta: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    n = delta.shape[0]
    return update_and_query(spec, S, jnp.arange(n, dtype=jnp.int32), delta)
