"""Fused streaming CS-Adam — the paper's Algorithm 4 in ONE HBM pass.

The per-item algorithm touches each sketch 3× (query, update, query) and
the reference implementation launches separate gather / scatter ops — four
sketch traversals per moment per step.  This kernel fuses the whole Adam
row update:

    m_old = median_j  s_j(i)·M[j, h_j(i)]         (VMEM, DMA'd in)
    Δm    = (1−β₁)(g_i − m_old);  M rows += s_j·Δm (DMA'd back)
    v_old = min_j  V[j, h'_j(i)]
    Δv    = (1−β₂)(g_i² − v_old);  V rows += Δv
    upd_i = −η·(m_old+Δm)/bc₁ / (√((v_old+Δv)⁺/bc₂) + ε)

so each sketch row makes exactly one HBM→VMEM→HBM round trip per item.

Because items are *streamed* (grid step = item, later items observe earlier
items' sketch writes — the paper's exact per-item semantics), the sketch
cannot go through the double-buffered BlockSpec pipeline: a block fetched
ahead could be stale.  Instead the sketches live in ``pl.ANY`` (HBM) and
the kernel issues explicit ``pltpu.async_copy`` read-modify-write DMAs per
item, addressed by scalar-prefetched hash buckets.  The sequential TPU grid
makes this race-free without atomics (DESIGN.md §3).

Oracle: ``ref.adam_fused_ref`` (a ``lax.scan`` over items).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.sketch import median_rows


def _adam_kernel(depth: int, track_m: bool,
                 bm_ref, sm_ref, bv_ref,          # scalar prefetch (SMEM)
                 hyper, g_blk,                    # SMEM hypers, VMEM grad row
                 M_any, V_any,                    # sketches, pl.ANY (HBM)
                 M_out, V_out, upd_out,           # aliased outs + updates
                 m_scr, v_scr, sem):              # scratch VMEM + DMA sem
    i = pl.program_id(0)
    lr, b1, b2, eps, bc1, bc2 = (hyper[0], hyper[1], hyper[2], hyper[3],
                                 hyper[4], hyper[5])
    g = g_blk[0, :]

    # ---- DMA in all sketch rows for this item --------------------------
    copies = []
    if track_m:
        for j in range(depth):
            c = pltpu.async_copy(
                M_out.at[j, pl.ds(bm_ref[j, i], 1), :], m_scr.at[j], sem)
            copies.append(c)
    for j in range(depth):
        c = pltpu.async_copy(
            V_out.at[j, pl.ds(bv_ref[j, i], 1), :], v_scr.at[j], sem)
        copies.append(c)
    for c in copies:
        c.wait()

    # ---- 1st moment (count-sketch, signed median) ----------------------
    if track_m:
        m_old = median_rows([m_scr[j, 0, :] * sm_ref[j, i]
                             for j in range(depth)])
        dm = (1.0 - b1) * (g - m_old)
        for j in range(depth):
            m_scr[j, 0, :] = m_scr[j, 0, :] + sm_ref[j, i] * dm
        mhat = (m_old + dm) / bc1
    else:
        mhat = g

    # ---- 2nd moment (count-min, min) ------------------------------------
    vrows = [v_scr[j, 0, :] for j in range(depth)]
    v_old = functools.reduce(jnp.minimum, vrows)
    dv = (1.0 - b2) * (g * g - v_old)
    for j in range(depth):
        v_scr[j, 0, :] = v_scr[j, 0, :] + dv
    v_new = jnp.maximum(v_old + dv, 0.0)

    upd_out[0, :] = (-lr * mhat / (jnp.sqrt(v_new / bc2) + eps)).astype(
        upd_out.dtype)

    # ---- DMA back --------------------------------------------------------
    copies = []
    if track_m:
        for j in range(depth):
            c = pltpu.async_copy(
                m_scr.at[j], M_out.at[j, pl.ds(bm_ref[j, i], 1), :], sem)
            copies.append(c)
    for j in range(depth):
        c = pltpu.async_copy(
            v_scr.at[j], V_out.at[j, pl.ds(bv_ref[j, i], 1), :], sem)
        copies.append(c)
    for c in copies:
        c.wait()


def cs_adam_fused(M: Optional[jnp.ndarray], V: jnp.ndarray,
                  bm: Optional[jnp.ndarray], sm: Optional[jnp.ndarray],
                  bv: jnp.ndarray, g: jnp.ndarray, *,
                  lr: float, b1: float, b2: float, eps: float,
                  bc1: float, bc2: float,
                  interpret: bool = False
                  ) -> Tuple[Optional[jnp.ndarray], jnp.ndarray, jnp.ndarray]:
    """Returns (M', V', param_update_rows).  ``M``/``bm``/``sm`` may be None
    for the β₁=0 (RMSProp / Theorem 5.1) variant."""
    depth, w, d = V.shape
    k = g.shape[0]
    track_m = M is not None
    if not track_m:
        # keep the kernel signature static: feed V twice, ignore the M slots
        M_in, bm_in, sm_in = V, bv, jnp.ones_like(bv, jnp.float32)
    else:
        M_in, bm_in, sm_in = M, bm, sm.astype(jnp.float32)

    hyper = jnp.array([lr, b1, b2, eps, bc1, bc2], jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,      # bm, sm, bv
        grid=(k,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # hyper
            pl.BlockSpec((1, d), lambda i, *_: (i, 0)),  # grad row
            pl.BlockSpec(memory_space=pl.ANY),       # M (HBM)
            pl.BlockSpec(memory_space=pl.ANY),       # V (HBM)
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),       # M'
            pl.BlockSpec(memory_space=pl.ANY),       # V'
            pl.BlockSpec((1, d), lambda i, *_: (i, 0)),  # updates
        ],
        scratch_shapes=[
            pltpu.VMEM((depth, 1, d), jnp.float32),
            pltpu.VMEM((depth, 1, d), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    fn = pl.pallas_call(
        functools.partial(_adam_kernel, depth, track_m),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(M_in.shape, M_in.dtype),
            jax.ShapeDtypeStruct(V.shape, V.dtype),
            jax.ShapeDtypeStruct((k, d), jnp.float32),
        ],
        # alias M (operand 5 = 3 prefetch + hyper + g) and V (operand 6)
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
    )
    M_out, V_out, upd = fn(bm_in, sm_in, bv, hyper, g, M_in, V)
    return (M_out if track_m else None), V_out, upd
