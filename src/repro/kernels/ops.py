"""Jit'd wrappers for the sketch kernels.

Each function runs exactly the implementation its name (or registry
backend) says: a Pallas kernel compiled for the TPU, the same kernel under
the Pallas interpreter only when asked for by name ('interpret' backend,
``interpret=True``, ``force='interpret'``), or a pure-jnp form.  Which
backend serves a sketch is decided once, by ``registry.resolve``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import quantize as qz
from repro.core import sketch as cs
from repro.core.sketch import SketchSpec
from repro.kernels import dedup as dd
from repro.kernels import ref
from repro.kernels.cs_adam import cs_adam_fused
from repro.kernels.cs_adam_tiled import DEFAULT_TILE, cs_adam_tiled
from repro.kernels.cs_ema_tiled import DEFAULT_TILE as EMA_TILE, cs_ema_tiled
from repro.kernels.cs_query import cs_query
from repro.kernels.cs_update import cs_update


def _use_kernel(force: Optional[str]) -> Tuple[bool, bool]:
    """(run the Pallas kernel, under the interpreter) for ``force``:
    None = the compiled kernel on a TPU host and the jnp oracle elsewhere,
    'pallas' = the compiled kernel, 'interpret' = the interpreter."""
    if force not in (None, "pallas", "interpret"):
        raise ValueError(f"force={force!r}: expected None, 'pallas' or "
                         "'interpret'")
    if force is None:
        return jax.default_backend() == "tpu", False
    return True, force == "interpret"


def _lowp(spec: SketchSpec) -> bool:
    """True when the spec stores cells below f32 (bf16 or int8)."""
    return jnp.dtype(spec.dtype) != jnp.float32


def _addressing(spec: SketchSpec, ids: jnp.ndarray):
    fam = spec.family
    buckets = fam.bucket(ids)
    signs = fam.sign(ids) if spec.signed else None
    return buckets, signs


def sketch_query(spec: SketchSpec, S: jnp.ndarray, ids: jnp.ndarray, *,
                 force: Optional[str] = None) -> jnp.ndarray:
    """QUERY rows ``ids``; Pallas gather kernel on TPU, jnp gather off-TPU
    (``force``: see ``_use_kernel``)."""
    if _lowp(spec):
        # low-precision cells: the core gather dequantizes in-register
        return cs.query(spec, S, ids)
    buckets, signs = _addressing(spec, ids)
    kernel, interpret = _use_kernel(force)
    if kernel:
        return cs_query(S, buckets, signs, interpret=interpret)
    return ref.cs_query_ref(S, buckets, signs)


def sketch_update(spec: SketchSpec, S: jnp.ndarray, ids: jnp.ndarray,
                  delta: jnp.ndarray, *,
                  force: Optional[str] = None) -> jnp.ndarray:
    """UPDATE rows ``ids`` with ``delta``; sorted-scatter kernel on TPU
    (``force``: see ``_use_kernel``)."""
    if _lowp(spec):
        # low-precision cells: stochastic-rounding write in the core
        return cs.update(spec, S, ids, delta)
    buckets, signs = _addressing(spec, ids)
    kernel, interpret = _use_kernel(force)
    if kernel:
        return cs_update(S, buckets, signs, delta, interpret=interpret)
    return ref.cs_update_ref(S, buckets, signs, delta)


def _adam_hypers(step: jnp.ndarray, lr, b1: float, b2: float):
    """(eta, bc1, bc2) — schedule + bias corrections at ``step``."""
    t = step.astype(jnp.float32)
    eta = lr(step) if callable(lr) else jnp.asarray(lr, jnp.float32)
    return eta, 1.0 - b1 ** t, 1.0 - b2 ** t


def _adam_addressing(spec_m: Optional[SketchSpec], spec_v: SketchSpec,
                     ids: jnp.ndarray):
    if spec_m is not None:
        bm, sm = _addressing(spec_m, ids)
    else:
        bm, sm = None, None
    bv, _ = _addressing(spec_v, ids)
    return bm, sm, bv


def adam_rows_ref(spec_m: Optional[SketchSpec], spec_v: SketchSpec,
                  M: Optional[jnp.ndarray], V: jnp.ndarray,
                  ids: jnp.ndarray, g: jnp.ndarray, step: jnp.ndarray, *,
                  lr, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8
                  ) -> Tuple[Optional[jnp.ndarray], jnp.ndarray, jnp.ndarray]:
    """'ref' backend: pure-jnp ``lax.scan`` per-item oracle (paper Alg. 4).

    Low-precision cells delegate to 'xla' — the per-item scan operates on
    raw f32 sketch rows, and re-rounding after every row would compound
    SR noise ``k`` times per step; the batch form rounds once."""
    if _lowp(spec_v) or (spec_m is not None and _lowp(spec_m)):
        return adam_rows_xla(spec_m, spec_v, M, V, ids, g, step, lr=lr,
                             b1=b1, b2=b2, eps=eps)
    bm, sm, bv = _adam_addressing(spec_m, spec_v, ids)
    eta, bc1, bc2 = _adam_hypers(step, lr, b1, b2)
    return ref.adam_fused_ref(M, V, bm, sm, bv, g, lr=eta, b1=b1, b2=b2,
                              eps=eps, bc1=bc1, bc2=bc2)


def adam_rows_stream(spec_m: Optional[SketchSpec], spec_v: SketchSpec,
                     M: Optional[jnp.ndarray], V: jnp.ndarray,
                     ids: jnp.ndarray, g: jnp.ndarray, step: jnp.ndarray, *,
                     lr, b1: float = 0.9, b2: float = 0.999,
                     eps: float = 1e-8, interpret: bool = False
                     ) -> Tuple[Optional[jnp.ndarray], jnp.ndarray, jnp.ndarray]:
    """'stream' backend: one-item-per-grid-step Pallas kernel — exact
    per-item semantics, sequential over the batch.  float32 cells only
    (the registry refuses others)."""
    bm, sm, bv = _adam_addressing(spec_m, spec_v, ids)
    eta, bc1, bc2 = _adam_hypers(step, lr, b1, b2)
    return cs_adam_fused(M, V, bm, sm, bv, g, lr=eta, b1=b1, b2=b2,
                         eps=eps, bc1=bc1, bc2=bc2, interpret=interpret)


def adam_rows_xla(spec_m: Optional[SketchSpec], spec_v: SketchSpec,
                  M: Optional[jnp.ndarray], V: jnp.ndarray,
                  ids: jnp.ndarray, g: jnp.ndarray, step: jnp.ndarray, *,
                  lr, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8
                  ) -> Tuple[Optional[jnp.ndarray], jnp.ndarray, jnp.ndarray]:
    """'xla' backend: the dedup pre-pass + the vectorized jnp batch step —
    no Pallas, fully parallel under XLA.  Identical to 'tiled' with one
    tile spanning the whole batch; the per-host best off-TPU."""
    if ids.shape[0] == 0:
        return M, V, jnp.zeros(g.shape, jnp.float32)
    eta, bc1, bc2 = _adam_hypers(step, lr, b1, b2)
    with jax.named_scope("obs.dedup"):
        batch = dd.dedup_rows(ids, g)
    mask = batch.mask[:, None]
    uids, rows = batch.unique_ids, batch.rows
    # low-precision writes draw fresh rounding bits every step (a fixed
    # seed would re-apply the same rounding pattern and bias the EMA)
    sr_m = qz.step_seed(spec_m.seed, step) \
        if spec_m is not None and _lowp(spec_m) else None
    sr_v = qz.step_seed(spec_v.seed, step) if _lowp(spec_v) else None
    with jax.named_scope("obs.kernel"):
        if spec_m is not None:
            m_old = cs.query(spec_m, M, uids)
            dm = (1.0 - b1) * (rows - m_old) * mask
            M = cs.update(spec_m, M, uids, dm, sr_seed=sr_m)
            mhat = (m_old + dm) / bc1
        else:
            mhat = rows
        v_old = cs.query(spec_v, V, uids)
        dv = (1.0 - b2) * (rows * rows - v_old) * mask
        V = cs.update(spec_v, V, uids, dv, sr_seed=sr_v)
        vhat = jnp.maximum(v_old + dv, 0.0) / bc2
        upd = mask * (-eta) * mhat / (jnp.sqrt(vhat) + eps)
    with jax.named_scope("obs.apply"):
        return M, V, dd.scatter_back(batch, upd)


def adam_rows_tiled(spec_m: Optional[SketchSpec], spec_v: SketchSpec,
                    M: Optional[jnp.ndarray], V: jnp.ndarray,
                    ids: jnp.ndarray, g: jnp.ndarray, step: jnp.ndarray, *,
                    lr, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                    tile: int = DEFAULT_TILE, interpret: bool = False
                    ) -> Tuple[Optional[jnp.ndarray], jnp.ndarray, jnp.ndarray]:
    """'tiled' backend: dedup + segment-sum pre-pass, then the batch-parallel
    ``cs_adam_tiled`` kernel over TILE collision-free rows per grid step.

    Duplicate ids are merged up front (their gradient rows are what a dense
    gradient would have summed anyway); the resulting updates are scattered
    back so that only the FIRST occurrence of each id carries the update —
    ``params.at[ids].add(upd)`` applies it exactly once.  float32 cells
    only; the registry sends other sketches to 'xla' by name
    (``tiled_refusal``).
    """
    if ids.shape[0] == 0:
        return M, V, jnp.zeros(g.shape, jnp.float32)
    eta, bc1, bc2 = _adam_hypers(step, lr, b1, b2)
    with jax.named_scope("obs.dedup"):
        batch = dd.pad_to_multiple(dd.dedup_rows(ids, g), tile)
        bm, sm, bv = _adam_addressing(spec_m, spec_v, batch.unique_ids)
    with jax.named_scope("obs.kernel"):
        M_out, V_out, upd_u = cs_adam_tiled(
            M, V, bm, sm, bv, batch.rows, lr=eta, b1=b1, b2=b2, eps=eps,
            bc1=bc1, bc2=bc2, n_valid=batch.n_unique, tile=tile,
            interpret=interpret)
    with jax.named_scope("obs.apply"):
        return M_out, V_out, dd.scatter_back(batch, upd_u)


# ---------------------------------------------------------------------------
# Fused dense-path update_read (the AuxStore protocol's one-pass EMA op)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=256)
def _cached_addressing(spec: SketchSpec, n: int):
    """Bucket/sign tables for the dense row set arange(n), computed ONCE
    per (spec, n) on the host and reused as jit constants.  The dense
    path addresses the same rows every step — the composed fallback
    re-hashes them twice per step (query + update); the fused backends
    pay zero hash compute.  Evaluated under compile-time-eval so an
    enclosing trace cannot stage (or leak tracers into) the cache."""
    import numpy as np
    with jax.ensure_compile_time_eval():
        ids = jnp.arange(n, dtype=jnp.int32)
        fam = spec.family
        buckets = np.asarray(jax.device_get(fam.bucket(ids)))
        signs = (np.asarray(jax.device_get(fam.sign(ids)))
                 if spec.signed else None)
    # cache NUMPY arrays: converting to jnp here under an active trace
    # would cache a tracer; numpy constants embed cleanly in any graph
    return buckets, signs


def _ema_addressing(spec: SketchSpec, ids: jnp.ndarray):
    """(buckets, signs) for ``ids`` — the host-cached constant tables when
    ``ids`` is concretely the dense row set arange(n), hashed in-graph
    otherwise.  The detection is pure numpy, safe under an outer trace."""
    import numpy as np
    n = int(ids.shape[0])
    if n and not isinstance(ids, jax.core.Tracer):
        idv = np.asarray(jax.device_get(ids))
        if bool((idv == np.arange(n, dtype=idv.dtype)).all()):
            return _cached_addressing(spec, n)
    fam = spec.family
    return fam.bucket(ids), (fam.sign(ids) if spec.signed else None)


def _gather_lowp(spec: SketchSpec, S, b, s):
    """Depth-unrolled dequantizing gather: per-hash-row (k, dim) f32 rows
    at buckets ``b``, sign-multiplied when signed.  The one gather form
    both low-precision fused backends share (bit-identity by construction)."""
    rows = []
    for j in range(spec.depth):
        if spec.quantized:
            blk = b[j] // spec.scale_block
            sc = S.scales[j][blk][:, None]
            r = S.cells[j][b[j]].astype(jnp.float32) * sc
            if not spec.signed:
                # half-ulp floor on unsigned reads — same form as
                # cs.query's (resolution limit of the quantizer;
                # protects Adam/Adagrad denominators, see sketch.query)
                r = jnp.maximum(r, 0.5 * sc)
        else:
            r = S[j][b[j]].astype(jnp.float32)
        if spec.signed:
            r = r * s[j][:, None].astype(jnp.float32)
        rows.append(r)
    return rows


def _ema_update_read_lowp(spec: SketchSpec, S, ids: jnp.ndarray,
                          x: jnp.ndarray, *, beta: float, scale: float,
                          mask, sr_seed) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Low-precision fused update_read — the SHARED implementation behind
    the 'ref' and 'xla' registry rows for bf16/int8 cells (both route
    here, so they are bit-identical and 'ref' stays the pinnable oracle).

    Dense-path write regime (DESIGN.md §18): the increments are scattered
    into a per-depth f32 delta, added to the dequantized cells
    elementwise, and the whole sketch is re-rounded stochastically —
    int8 refreshes its per-(depth, block) absmax scales every step, bf16
    re-rounds in place (exact on untouched cells: bf16-representable
    values truncate without carry, so only touched cells change)."""
    sr_seed = cs.sr_seed_or_default(spec, sr_seed)
    b, s = _ema_addressing(spec, ids)
    rows = _gather_lowp(spec, S, b, s)
    if spec.signed:
        est_old = cs.median_rows(rows)
    else:
        est_old = functools.reduce(jnp.minimum, rows)
    d = cs.ema_delta(est_old, x, beta, scale)
    if mask is not None:
        d = d * mask
    w = spec.width
    inc = []
    for j in range(spec.depth):
        u = (s[j][:, None].astype(jnp.float32) * d) if spec.signed else d
        inc.append(jnp.zeros((w, spec.dim), jnp.float32).at[b[j]].add(u))
    inc = jnp.stack(inc)
    if spec.quantized:
        dense = qz.dequantize(S, spec.scale_block) + inc
        S = qz.quantize(dense, sr_seed, scale_block=spec.scale_block)
    else:
        bits = qz.cell_bits(sr_seed, qz._lin_index(S.shape))
        S = qz.sr_bfloat16(S.astype(jnp.float32) + inc, bits)
    return S, est_old + d


def ema_update_read_ref(spec: SketchSpec, S: jnp.ndarray, ids: jnp.ndarray,
                        x: jnp.ndarray, *, beta: float, scale: float,
                        mask: Optional[jnp.ndarray] = None,
                        sr_seed=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """'ref' backend: the composed primitives, one-shot — query, the
    shared ``ema_delta`` form, update.  The oracle the fused paths are
    parity-tested against (bit-identical to the composed fallback).

    Low-precision cells use the shared dense-regime form (fresh absmax
    scales for int8) rather than the composed sparse-update (held-scale
    monotone growth) — the fused op IS the dense path, and sharing one
    form keeps 'ref' bit-identical to 'xla' at every cell dtype."""
    if _lowp(spec):
        return _ema_update_read_lowp(spec, S, ids, x, beta=beta,
                                     scale=scale, mask=mask, sr_seed=sr_seed)
    est_old = cs.query(spec, S, ids)
    d = cs.ema_delta(est_old, x, beta, scale)
    if mask is not None:
        d = d * mask
    S = cs.update(spec, S, ids, d)
    return S, est_old + d


def ema_update_read_xla(spec: SketchSpec, S: jnp.ndarray, ids: jnp.ndarray,
                        x: jnp.ndarray, *, beta: float, scale: float,
                        mask: Optional[jnp.ndarray] = None,
                        sr_seed=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """'xla' backend: one fused gather → ema_delta → scatter pass.

    Two hand-optimizations over the reference primitives, same values:

      * addressing is computed ONCE (the composed path hashes every id
        twice per step — query, then update again) and not at all for
        the dense arange(n) row set, whose bucket/sign tables are
        host-cached constants;
      * the depth axis is UNROLLED into per-hash-row gathers/scatters —
        XLA:CPU lowers a batched (vmap) gather/scatter an order of
        magnitude slower than ``depth`` flat ones, and the (depth, k,
        dim) temp blob becomes ``depth`` cache-sized (k, dim) temps
        (EXPERIMENTS.md §FusedStore).

    The arithmetic is operation-for-operation the reference form
    (gather, sign multiply, pairwise median / min, the shared
    ``ema_delta``, sign-multiplied scatter-add), so the result is
    bit-identical to 'ref' and the composed fallback.  Low-precision
    cells route through the shared quantized form (same function 'ref'
    uses — dequantizing gathers, one stochastic re-round per step)."""
    if _lowp(spec):
        return _ema_update_read_lowp(spec, S, ids, x, beta=beta,
                                     scale=scale, mask=mask, sr_seed=sr_seed)
    b, s = _ema_addressing(spec, ids)
    depth = spec.depth
    rows = []
    for j in range(depth):
        r = S[j][b[j]]                                    # (k, dim)
        if spec.signed:
            r = r * s[j][:, None].astype(S.dtype)
        rows.append(r)
    if spec.signed:
        est_old = cs.median_rows(rows)
    else:
        est_old = functools.reduce(jnp.minimum, rows)
    d = cs.ema_delta(est_old, x, beta, scale)
    if mask is not None:
        d = d * mask
    out = []
    for j in range(depth):
        u = (s[j][:, None].astype(S.dtype) * d.astype(S.dtype)
             if spec.signed else d.astype(S.dtype))
        out.append(S[j].at[b[j]].add(u))
    return jnp.stack(out), est_old + d


def ema_update_read_tiled(spec: SketchSpec, S: jnp.ndarray, ids: jnp.ndarray,
                          x: jnp.ndarray, *, beta: float, scale: float,
                          mask: Optional[jnp.ndarray] = None,
                          tile: int = EMA_TILE, interpret: bool = False,
                          sr_seed=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """'tiled' backend: the ``cs_ema_tiled`` Pallas kernel — TILE rows per
    sequential grid step, sketch row groups DMA'd from HBM in one
    overlapped burst per tile.  Batch semantics within a tile, streaming
    across tiles (exact vs 'ref' when no two rows share a bucket;
    estimator-noise tolerance otherwise — DESIGN.md §14).

    bf16 cells run IN the kernel: row groups DMA in/out as bf16, compute
    is f32 in VMEM, and write-back stochastically re-rounds with the same
    counter-hash bits the xla path derives — touched rows match 'xla'
    bit-for-bit on collision-free row sets.  int8 cells, and rows that
    are not whole 128-lane tiles, are refused by the registry, which
    sends them to 'xla' by name: per-(depth, block) absmax scale refresh
    needs a whole-sketch view a touched-rows kernel doesn't have
    (DESIGN.md §18)."""
    k = int(ids.shape[0])
    if k == 0:
        return S, jnp.zeros(x.shape, jnp.float32)
    seed = None
    if jnp.dtype(spec.dtype) == jnp.bfloat16:
        seed = cs.sr_seed_or_default(spec, sr_seed)
    b, s = _ema_addressing(spec, ids)
    m = jnp.ones((k, 1), jnp.float32) if mask is None \
        else jnp.broadcast_to(mask.astype(jnp.float32), (k, 1))
    pad = (-k) % tile
    if pad:
        b = jnp.pad(b, ((0, 0), (0, pad)))
        s = None if s is None else jnp.pad(s, ((0, 0), (0, pad)),
                                           constant_values=1.0)
        x = jnp.pad(x, ((0, pad), (0, 0)))
        m = jnp.pad(m, ((0, pad), (0, 0)))
    S, est = cs_ema_tiled(S, b, s, x, m, beta=beta, scale=scale,
                          n_valid=k, tile=tile, interpret=interpret,
                          sr_seed=seed)
    return S, est[:k]


# ---------------------------------------------------------------------------
# Shard-local slab ops (DESIGN.md §17)
# ---------------------------------------------------------------------------
# The sharded optimizer body runs these on each shard's (depth, lw, dim)
# slab under shard_map; ids outside the slab are masked, so concatenating
# the per-shard updates (resp. psum-ing the per-shard gathers) over the
# shard axis reproduces the full-width op bit-exactly.  'ref' is the
# vmapped form in core.sketch; 'xla' unrolls the depth axis into flat
# gathers/scatters exactly like ``ema_update_read_xla`` (same arithmetic,
# so bit-identical — XLA:CPU lowers flat ops far faster than batched).


def _slab_addressing(spec: SketchSpec, ids: jnp.ndarray, shard):
    lw = spec.local_width
    local = spec.family.bucket(ids) - jnp.asarray(shard, jnp.int32) * lw
    own = (local >= 0) & (local < lw)
    return jnp.where(own, local, lw), own


def slab_update_xla(spec: SketchSpec, slab: jnp.ndarray, ids: jnp.ndarray,
                    delta: jnp.ndarray, shard) -> jnp.ndarray:
    """'xla' backend of ``sketch.update_slab``: depth-unrolled masked
    scatter-add into the local slab (out-of-slab rows dropped)."""
    local, _ = _slab_addressing(spec, ids, shard)
    signs = spec.family.sign(ids) if spec.signed else None
    out = []
    for j in range(spec.depth):
        u = delta.astype(slab.dtype)
        if spec.signed:
            u = signs[j][:, None].astype(slab.dtype) * u
        out.append(slab[j].at[local[j]].add(u, mode="drop"))
    return jnp.stack(out)


def slab_gather_xla(spec: SketchSpec, slab: jnp.ndarray, ids: jnp.ndarray,
                    shard) -> jnp.ndarray:
    """'xla' backend of ``sketch.gather_slab``: depth-unrolled gather of
    this shard's (unsigned, un-reduced) contributions — zeros off-slab,
    so a psum over the shard axis assembles the full (depth, k, dim)
    rows for ``sketch.finish_query``."""
    local, own = _slab_addressing(spec, ids, shard)
    lw = spec.local_width
    rows = []
    for j in range(spec.depth):
        r = slab[j][jnp.minimum(local[j], lw - 1)]
        rows.append(jnp.where(own[j][:, None], r,
                              jnp.zeros((), dtype=slab.dtype)))
    return jnp.stack(rows)
