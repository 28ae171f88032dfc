"""Pallas-TPU sketch QUERY: gather ``depth`` random rows per item + reduce.

TPU adaptation of the paper's per-row gather (DESIGN.md §3):

  * Hash buckets are computed once on the VPU and handed to the kernel as a
    *scalar-prefetch* operand; ``BlockSpec.index_map`` reads them to stream
    exactly the needed ``(1, d)`` sketch rows HBM→VMEM.  The trailing
    ``d`` axis stays contiguous (lane dimension) — the "structured
    sparsity" of the paper's count-sketch tensor maps directly onto the
    TPU tiling.
  * The sketch is passed ``depth`` times (read-only aliases of the same
    buffer), one BlockSpec per hash row, so a grid step fetches all
    ``depth`` candidate rows for item ``i`` in parallel DMAs.
  * median-of-3 is computed as ``a+b+c−max−min`` (VPU ops, no sort).

Grid: ``(k,)`` — one step per queried item; reads are hazard-free so the
normal double-buffered pipeline applies.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.sketch import median_rows


def _query_kernel(depth: int, signed: bool, b_ref, *refs):
    # refs: depth sketch-row blocks (1,1,d), sign block (depth,1) [if signed],
    #       out block (1, d)
    rows = [refs[j][0, 0, :] for j in range(depth)]
    if signed:
        sign_ref = refs[depth]
        out_ref = refs[depth + 1]
        rows = [rows[j] * sign_ref[j, 0] for j in range(depth)]
    else:
        out_ref = refs[depth]
    if signed:
        red = median_rows(rows)
    else:
        red = functools.reduce(jnp.minimum, rows)
    out_ref[0, :] = red.astype(out_ref.dtype)


def cs_query(S: jnp.ndarray, buckets: jnp.ndarray,
             signs: Optional[jnp.ndarray], *,
             interpret: bool = False) -> jnp.ndarray:
    """S (v,w,d); buckets (v,k) int32; signs (v,k) f32 or None (count-min).

    Returns estimates (k, d).  Matches ``ref.cs_query_ref`` exactly.
    """
    v, w, d = S.shape
    k = buckets.shape[1]
    signed = signs is not None

    def s_index(j):
        return lambda i, b: (j, b[j, i], 0)

    in_specs = [pl.BlockSpec((1, 1, d), s_index(j)) for j in range(v)]
    ins = [S] * v
    if signed:
        in_specs.append(pl.BlockSpec((v, 1), lambda i, b: (0, i)))
        ins.append(signs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, d), lambda i, b: (i, 0)),
    )
    fn = pl.pallas_call(
        functools.partial(_query_kernel, v, signed),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k, d), S.dtype),
        interpret=interpret,
    )
    return fn(buckets, *ins)
