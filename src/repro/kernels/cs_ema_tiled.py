"""Tiled fused ``update_read`` for ONE sketch tensor — the dense hot path.

The ``AuxStore`` protocol's fused op (DESIGN.md §14)

    update_read(S, x, β, scale)  ≡  est_old = query(S, rows)
                                    d       = ema_delta(est_old, x, β, scale)
                                    S'      = update(S, rows, d)
                                    est     = est_old + d

runs one moment of the dense-gradient path in a single pass over the
sketch: per grid step, gather the sketch rows of ``TILE`` entries at every
depth, form the median/min estimate, the linear-EMA increment, and the
scatter-back — the single-store sibling of the fused sparse-rows kernel
(``cs_adam_tiled.py``), sharing its machinery (``row_groups.py``):

  * the ``x`` (gradient / g²) tile and the ``est`` output tile move
    through the double-buffered BlockSpec pipeline; the sketch stays in
    ``pl.ANY`` (HBM) and each tile DMAs the aligned row group holding
    every addressed bucket, all in one overlapped burst;
  * bucket collisions inside a tile are folded through the group
    placement matmul, so entries that share a group write back identical
    fully-accumulated groups;
  * estimates read the sketch as of the START of the tile: batch
    semantics within a tile, streaming across tiles (tile t+1 observes
    tile t's writes through the sequential TPU grid, and call c+1 of a
    long batch observes call c's) — bit-identical to the composed
    one-shot fallback on collision-free row sets (the dense path's rows
    are ``arange(n)``: always id-unique, so only *bucket* collisions
    across tiles differ, by estimator noise).

``beta``/``scale`` are static floats and the increment uses the shared
``sketch.ema_delta`` forms, so the arithmetic matches the composed
fallback operation-for-operation.  Rows at positions ≥ ``n_valid``
(tile padding) have mask 0: they add exactly zero to every bucket.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core import quantize as qz
from repro.core.sketch import ema_delta, median_rows
from repro.kernels import row_groups as rg

DEFAULT_TILE = 8


def _ema_kernel(depth: int, tile: int, signed: bool,
                beta: float, scale: float, width: int, bf16: bool,
                b_ref, s_ref, nv_ref,     # scalar prefetch (SMEM)
                x_blk, mask_blk,          # VMEM input tiles
                S_any,                    # sketch, pl.ANY (HBM)
                S_out, est_out,           # aliased out + estimate tile
                stage, sem):              # (depth, tile·G, dim) VMEM + DMA
    t = pl.program_id(0)
    base = t * tile
    g = rg.group_rows(stage.dtype)
    grp = [rg.Groups([b_ref[j, base + r] for r in range(tile)], tile, g)
           for j in range(depth)]

    # ---- DMA in every addressed row group, one overlapped burst ---------
    copies = []
    for j in range(depth):
        copies += rg.dma_groups(S_out, stage, j, grp[j], sem, to_hbm=False)
    for c in copies:
        c.wait()

    x = x_blk[:, :]                                          # (tile, d)
    row_pos = base + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    valid = (row_pos < nv_ref[0]).astype(jnp.float32)        # (tile, 1)
    msk = mask_blk[:, :] * valid                             # (tile, 1)

    blocks = [stage[j].astype(jnp.float32) for j in range(depth)]
    rows = [grp[j].read(blocks[j]) for j in range(depth)]

    # ---- estimate: median (signed) / min (count-min) over depth ----------
    if signed:
        sgn = [rg.row_vec([s_ref[j, base + r] for r in range(tile)], tile,
                          dtype=jnp.float32) for j in range(depth)]
        est_old = median_rows([rows[j] * sgn[j] for j in range(depth)])
    else:
        est_old = functools.reduce(jnp.minimum, rows)

    d = ema_delta(est_old, x, beta, scale) * msk

    # ---- scatter-add through the group placement matmul -----------------
    for j in range(depth):
        new = grp[j].add(blocks[j], sgn[j] * d if signed else d)
        if bf16:
            # stochastic re-round with the SAME counter-hash bits the xla
            # path derives from the cell's linear index, so touched rows
            # match ema_update_read_xla bit-for-bit (DESIGN.md §18);
            # untouched rows of a group are bf16 values and round to
            # themselves
            seed = nv_ref[1].astype(jnp.uint32)
            dim = new.shape[1]
            col = jax.lax.broadcasted_iota(jnp.uint32, new.shape, 1)
            lin = (jnp.uint32(j * width) + grp[j].row_ids()) \
                * jnp.uint32(dim) + col
            stage[j] = qz.sr_bfloat16(new, qz.cell_bits(seed, lin))
        else:
            stage[j] = new

    est_out[:, :] = (est_old + d).astype(est_out.dtype)

    # ---- DMA back (shared groups write identical accumulated rows) -------
    copies = []
    for j in range(depth):
        copies += rg.dma_groups(S_out, stage, j, grp[j], sem, to_hbm=True)
    for c in copies:
        c.wait()


def _one_call(S, b, s_in, nv, x, mask, *, tile, signed, beta, scale,
              interpret):
    depth, w, dim = S.shape
    k = x.shape[0]
    bf16 = S.dtype == jnp.bfloat16
    g = rg.group_rows(S.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,      # b, s, (n_valid, seed)
        grid=(k // tile,),
        in_specs=[
            pl.BlockSpec((tile, dim), lambda t, *_: (t, 0)),  # x tile
            pl.BlockSpec((tile, 1), lambda t, *_: (t, 0)),    # mask tile
            pl.BlockSpec(memory_space=pl.ANY),                # S (HBM)
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),                # S'
            pl.BlockSpec((tile, dim), lambda t, *_: (t, 0)),  # est tile
        ],
        scratch_shapes=[pltpu.VMEM((depth, tile * g, dim), S.dtype),
                        pltpu.SemaphoreType.DMA],
    )
    fn = pl.pallas_call(
        functools.partial(_ema_kernel, depth, tile, signed,
                          float(beta), float(scale), w, bf16),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(S.shape, S.dtype),
            jax.ShapeDtypeStruct((k, dim), jnp.float32),
        ],
        # alias S (operand 5 = 3 prefetch + x + mask) onto output 0
        input_output_aliases={5: 0},
        name="cs_ema_tiled",
        interpret=interpret,
    )
    return fn(b, s_in, nv, x, mask, S)


def cs_ema_tiled(S: jnp.ndarray, b: jnp.ndarray, s, x: jnp.ndarray,
                 mask: jnp.ndarray, *, beta: float, scale: float,
                 n_valid=None, tile: int = DEFAULT_TILE,
                 interpret: bool = False, sr_seed=None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused EMA update_read over ``k`` rows of one (depth, width, dim)
    sketch.

    S           (depth, width, dim) sketch tensor (float32 or bfloat16;
                width a multiple of ``row_groups.group_rows``)
    b           (depth, k) int32 bucket addresses
    s           (depth, k) float32 signs, or None for count-min
    x           (k, dim) input rows (gradient or g², float32)
    mask        (k, 1) float32 row mask (lazy/row-active × validity)
    n_valid     rows at positions >= n_valid are padding (zero writes,
                zero estimates).  Defaults to k.
    tile        rows per grid step; k must be a multiple.
    sr_seed     uint32 stochastic-rounding seed — required for bf16
                sketches (row groups DMA as bf16, accumulate in f32, and
                write back through ``quantize.sr_bfloat16``; rows nobody
                touched round to their exact original value).  Ignored
                for f32.

    Returns ``(S', est)`` with ``est[k, dim]`` = est_old + Δ (batch
    semantics within a tile, streaming across tiles and calls).
    """
    depth, w, dim = S.shape
    k = x.shape[0]
    if k % tile != 0:
        raise ValueError(f"k={k} must be a multiple of tile={tile}")
    why = rg.kernel_refusal(w, S.dtype)
    if why is not None:
        raise ValueError(f"cs_ema_tiled cannot run this sketch: {why}")
    bf16 = S.dtype == jnp.bfloat16
    if bf16 and sr_seed is None:
        raise ValueError("bf16 cs_ema_tiled needs an sr_seed "
                         "(quantize.step_seed)")
    signed = s is not None
    s_in = s.astype(jnp.float32) if signed else jnp.ones_like(b, jnp.float32)
    n_valid = jnp.asarray(k if n_valid is None else n_valid, jnp.int32)
    seed = (jnp.asarray(sr_seed, jnp.uint32).astype(jnp.int32) if bf16
            else jnp.int32(0))   # the seed rides the int32 row (bit pattern)

    per, n_calls = rg.split_calls(k, rg.rows_per_call(depth, 2, tile), tile)
    pad = per * n_calls - k
    if pad:
        b = jnp.pad(b, ((0, 0), (0, pad)))
        s_in = jnp.pad(s_in, ((0, 0), (0, pad)), constant_values=1.0)
        x = jnp.pad(x, ((0, pad), (0, 0)))
        mask = jnp.pad(mask, ((0, pad), (0, 0)))
    starts = jnp.arange(n_calls, dtype=jnp.int32) * per
    nv = jnp.stack([jnp.clip(n_valid - starts, 0, per),
                    jnp.broadcast_to(seed, (n_calls,))], axis=1)
    xs = (rg.chunk_rows(b, n_calls, 1), rg.chunk_rows(s_in, n_calls, 1), nv,
          rg.chunk_rows(x, n_calls, 0), rg.chunk_rows(mask, n_calls, 0))

    def call(S, c):
        return _one_call(S, *c, tile=tile, signed=signed, beta=beta,
                         scale=scale, interpret=interpret)

    S, est = rg.scan_calls(call, S, xs, n_calls)
    return S, est.reshape(n_calls * per, dim)[:k]
