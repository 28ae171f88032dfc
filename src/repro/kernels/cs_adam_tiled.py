"""Tiled batch-parallel CS-Adam — ``TILE`` collision-free rows per grid step.

The streaming kernel (``cs_adam.py``) advances ONE item per grid step so
that duplicate ids compose through the EMA exactly as in the paper's
per-item algorithm.  After the dedup pre-pass (``dedup.py``) the batch is
collision-free in id-space, and the per-item ordering no longer matters:
the batched step over the tile is algebraically identical for ids that
never share a sketch bucket (DESIGN.md §10).  That removes the throughput
ceiling:

  * the gradient rows and the parameter-update rows move through the
    normal double-buffered BlockSpec pipeline, ``TILE`` rows per step —
    the compiler overlaps the step ``t+1`` fetch with step ``t`` compute;
  * the sketches stay in ``pl.ANY`` (HBM) and each step issues all
    ``depth × TILE`` DMAs of the aligned row groups that hold the
    addressed buckets at once (overlapped, one wait; ``row_groups.py``),
    instead of the streaming kernel's per-item round trip;
  * the row update itself is vectorized over the (TILE, d) block on the
    VPU, with the depth-way median/min unchanged.

Bucket collisions *within* a tile (two unique ids hashing to the same
bucket of hash row ``j``) still need scatter-ADD semantics, which the
write-back DMAs alone cannot provide.  The kernel folds an intra-tile
segment-sum into the group placement matmul of ``row_groups.Groups``:

    write_j = gathered_groups_j + place_j @ contribution_j

Entries whose buckets share a row group then write back *identical*
fully-accumulated groups, so any DMA completion order is correct.  Estimates still read the
pre-tile sketch — batch semantics inside a tile, streaming semantics
across tiles (tile t+1 observes tile t's writes through the sequential
TPU grid; see cs_update.py for the same race-freedom argument), and
batches longer than one call's SMEM address budget run as consecutive
calls with the same streaming order.

Rows past ``n_valid`` (dedup/tile padding) contribute exactly zero to
every sketch bucket and emit zero update rows.  A tile that starts at or
past ``n_valid`` moves no sketch group and fetches no new gradient
block: it only writes its zero update rows.

Oracle: ``ref.adam_fused_ref`` on collision-free batches (exact);
``tests/test_backends.py`` quantifies the colliding-batch tolerance.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.sketch import median_rows
from repro.kernels import row_groups as rg

DEFAULT_TILE = 8


def _tiled_kernel(depth: int, tile: int, track_m: bool,
                  bm_ref, sm_ref, bv_ref, nv_ref,   # scalar prefetch (SMEM)
                  hyper, g_blk,                     # SMEM hypers, VMEM grads
                  M_any, V_any,                     # sketches, pl.ANY (HBM)
                  M_out, V_out, upd_out,            # aliased outs + updates
                  m_stage, v_stage, sem):           # group VMEM + DMA sem
    base = pl.program_id(0) * tile

    @pl.when(base < nv_ref[0])
    def _():
        _live_tile(depth, tile, track_m, base, bm_ref, sm_ref, bv_ref,
                   nv_ref, hyper, g_blk, M_out, V_out, upd_out, m_stage,
                   v_stage, sem)

    @pl.when(base >= nv_ref[0])
    def _():
        upd_out[:, :] = jnp.zeros(upd_out.shape, upd_out.dtype)


def _live_tile(depth, tile, track_m, base, bm_ref, sm_ref, bv_ref, nv_ref,
               hyper, g_blk, M_out, V_out, upd_out, m_stage, v_stage, sem):
    """One tile that holds live rows: DMA its groups in, update, DMA back."""
    g = rg.group_rows(jnp.float32)
    lr, b1, b2, eps, bc1, bc2 = (hyper[0], hyper[1], hyper[2], hyper[3],
                                 hyper[4], hyper[5])

    def groups(ref):
        return [rg.Groups([ref[j, base + r] for r in range(tile)], tile, g)
                for j in range(depth)]

    gm = groups(bm_ref) if track_m else []
    gv = groups(bv_ref)

    # ---- DMA in every addressed row group, one overlapped burst ---------
    copies = []
    for j in range(depth):
        if track_m:
            copies += rg.dma_groups(M_out, m_stage, j, gm[j], sem,
                                    to_hbm=False)
        copies += rg.dma_groups(V_out, v_stage, j, gv[j], sem, to_hbm=False)
    for c in copies:
        c.wait()

    g_rows = g_blk[:, :]                                    # (tile, d)
    row_pos = base + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    valid = (row_pos < nv_ref[0]).astype(jnp.float32)       # (tile, 1)

    # ---- 1st moment: median estimate, batched over the tile ---------------
    if track_m:
        sgn = [rg.row_vec([sm_ref[j, base + r] for r in range(tile)], tile,
                          dtype=jnp.float32) for j in range(depth)]
        blocks = [m_stage[j] for j in range(depth)]
        m_old = median_rows([gm[j].read(blocks[j]) * sgn[j]
                             for j in range(depth)])
        dm = (1.0 - b1) * (g_rows - m_old) * valid
        for j in range(depth):
            m_stage[j] = gm[j].add(blocks[j], sgn[j] * dm)
        mhat = (m_old + dm) / bc1
    else:
        mhat = g_rows

    # ---- 2nd moment: min estimate (count-min) ------------------------------
    blocks = [v_stage[j] for j in range(depth)]
    v_old = functools.reduce(jnp.minimum, [gv[j].read(blocks[j])
                                           for j in range(depth)])
    dv = (1.0 - b2) * (g_rows * g_rows - v_old) * valid
    for j in range(depth):
        v_stage[j] = gv[j].add(blocks[j], dv)
    v_new = jnp.maximum(v_old + dv, 0.0)

    upd_out[:, :] = (valid * (-lr) * mhat /
                     (jnp.sqrt(v_new / bc2) + eps)).astype(upd_out.dtype)

    # ---- DMA back (shared groups write identical accumulated rows) -------
    copies = []
    for j in range(depth):
        if track_m:
            copies += rg.dma_groups(M_out, m_stage, j, gm[j], sem,
                                    to_hbm=True)
        copies += rg.dma_groups(V_out, v_stage, j, gv[j], sem, to_hbm=True)
    for c in copies:
        c.wait()


def _one_call(M, V, bm, sm, bv, nv, g, hyper, *, tile, track_m, interpret):
    depth, w, d = V.shape
    k = g.shape[0]
    rows = tile * rg.group_rows(jnp.float32)

    def grad_tile(t, bm, sm, bv, nv):
        # a dead tile keeps the last live tile's block: nothing refetched
        return jnp.minimum(t, jnp.maximum(nv[0] - 1, 0) // tile), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,      # bm, sm, bv, n_valid
        grid=(k // tile,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),          # hyper
            pl.BlockSpec((tile, d), grad_tile),             # grad tile
            pl.BlockSpec(memory_space=pl.ANY),              # M (HBM)
            pl.BlockSpec(memory_space=pl.ANY),              # V (HBM)
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),              # M'
            pl.BlockSpec(memory_space=pl.ANY),              # V'
            pl.BlockSpec((tile, d), lambda t, *_: (t, 0)),  # updates
        ],
        scratch_shapes=[
            pltpu.VMEM((depth if track_m else 1, rows, d), jnp.float32),
            pltpu.VMEM((depth, rows, d), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    fn = pl.pallas_call(
        functools.partial(_tiled_kernel, depth, tile, track_m),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(M.shape, M.dtype),
            jax.ShapeDtypeStruct(V.shape, V.dtype),
            jax.ShapeDtypeStruct((k, d), jnp.float32),
        ],
        # alias M (operand 6 = 4 prefetch + hyper + g) and V (operand 7)
        input_output_aliases={6: 0, 7: 1},
        name="cs_adam_tiled",
        interpret=interpret,
    )
    return fn(bm, sm, bv, nv, hyper, g, M, V)


def cs_adam_tiled(M: Optional[jnp.ndarray], V: jnp.ndarray,
                  bm: Optional[jnp.ndarray], sm: Optional[jnp.ndarray],
                  bv: jnp.ndarray, g: jnp.ndarray, *,
                  lr: float, b1: float, b2: float, eps: float,
                  bc1: float, bc2: float,
                  n_valid=None, tile: int = DEFAULT_TILE,
                  interpret: bool = False
                  ) -> Tuple[Optional[jnp.ndarray], jnp.ndarray, jnp.ndarray]:
    """Batch-parallel CS-Adam over ``k`` COLLISION-FREE (deduplicated) rows.

    Same contract as ``cs_adam.cs_adam_fused`` plus:

    n_valid: rows at positions >= n_valid are padding — their gradients are
        ignored and their update rows are zero.  Defaults to ``k``.
    tile:   rows per grid step; ``k`` must be a multiple (use
        ``dedup.pad_to_multiple``).

    Sketches are float32 with a width that is a multiple of 8 rows.
    ``M``/``bm``/``sm`` may be None for the β₁=0 (RMSProp) variant.
    """
    depth, w, d = V.shape
    k = g.shape[0]
    if k % tile != 0:
        raise ValueError(f"k={k} must be a multiple of tile={tile} "
                         "(pad with dedup.pad_to_multiple)")
    track_m = M is not None
    for S in ((M, V) if track_m else (V,)):
        why = rg.kernel_refusal(S.shape[1], S.dtype)
        if why is None and S.dtype != jnp.float32:
            why = f"{S.dtype} cells (this kernel keeps float32)"
        if why is not None:
            raise ValueError(f"cs_adam_tiled cannot run this sketch: {why}")
    if not track_m:
        # keep the kernel signature static: a one-row dummy M, never DMA'd
        M, bm, sm = (jnp.zeros((1, 8, d), jnp.float32),
                     jnp.zeros_like(bv), jnp.ones_like(bv, jnp.float32))
    else:
        sm = sm.astype(jnp.float32)

    hyper = jnp.array([lr, b1, b2, eps, bc1, bc2], jnp.float32)
    n_valid = jnp.asarray(k if n_valid is None else n_valid, jnp.int32)
    per, n_calls = rg.split_calls(k, rg.rows_per_call(depth, 3, tile), tile)
    pad = per * n_calls - k
    if pad:
        bm, bv = (jnp.pad(a, ((0, 0), (0, pad))) for a in (bm, bv))
        sm = jnp.pad(sm, ((0, 0), (0, pad)), constant_values=1.0)
        g = jnp.pad(g, ((0, pad), (0, 0)))
    starts = jnp.arange(n_calls, dtype=jnp.int32) * per
    nv = jnp.clip(n_valid - starts, 0, per)[:, None]
    xs = (rg.chunk_rows(bm, n_calls, 1), rg.chunk_rows(sm, n_calls, 1),
          rg.chunk_rows(bv, n_calls, 1), nv, rg.chunk_rows(g, n_calls, 0))

    def call(carry, c):
        bm_c, sm_c, bv_c, nv_c, g_c = c
        M_c, V_c, upd = _one_call(carry[0], carry[1], bm_c, sm_c, bv_c,
                                  nv_c, g_c, hyper, tile=tile,
                                  track_m=track_m, interpret=interpret)
        return (M_c, V_c), upd

    (M, V), upd = rg.scan_calls(call, (M, V), xs, n_calls)
    return (M if track_m else None), V, upd.reshape(n_calls * per, d)[:k]
