"""Row-group addressing shared by the tiled sketch kernels.

A TPU keeps each (width, dim) hash row of a sketch in HBM as tiles of
(sublanes × 128 lanes): 8 rows for 32-bit cells, 16 for bfloat16.  Mosaic
refuses a DMA that moves ONE row out of such a tile (it accepts it only
when dim is exactly 128 f32 lanes), so the tiled kernels move the whole
aligned GROUP of ``group_rows(dtype)`` rows that holds each addressed
bucket, and pick the bucket's row out of the group in VMEM:

  * read:   ``rows = sel @ groups``  with ``sel[r, r·G + off_r] = 1``;
  * write:  ``groups += place @ contrib`` with
            ``place[r·G + s, r'] = [grp_r == grp_r'] · [s == off_r']``.

``place`` adds every contribution of the tile whose bucket falls in the
group of entry ``r`` into entry ``r``'s copy of that group, so two entries
that share a group (bucket collisions included) write back identical,
fully accumulated groups and the order in which their DMAs land does not
matter.  This generalizes the (tile, tile) bucket-equality matmul of the
one-row form; the values written are the same.  Both matmuls run at
``Precision.HIGHEST``: a one-hot f32 product is then exact.

The bucket addresses are scalar-prefetched into SMEM, which holds 1 MiB,
so one ``pallas_call`` takes at most ``rows_per_call`` rows; longer
batches run as a ``lax.scan`` of calls.  Consecutive calls see each
other's writes exactly as consecutive tiles do, so "batch within a tile,
streaming across tiles" holds for any batch length.

The kernel compiles for a TPU when dim is a multiple of 128 lanes and
the width a multiple of the group (``tiled_refusal``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST
LANES = 128
# SMEM budget for the scalar-prefetched addresses of one call: half of
# the 1 MiB SMEM, counting each (depth, rows) table padded to 8 rows
_SMEM_BUDGET = 512 * 1024


def group_rows(dtype) -> int:
    """Rows per HBM tile of a sketch with ``dtype`` cells (8 for f32,
    16 for bf16)."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def kernel_refusal(width: int, dtype) -> Optional[str]:
    """Why the kernel body cannot run a (depth, width, ·) sketch of
    ``dtype`` cells at all (None when it can).  Interpret mode accepts
    what this accepts."""
    dt = jnp.dtype(dtype)
    if dt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return f"{dt.name} cells (the kernel keeps float32 or bfloat16)"
    g = group_rows(dt)
    if width % g:
        return (f"width {width} is not a multiple of the {g}-row HBM "
                f"group of {dt.name} cells")
    return None


def tiled_refusal(dim: int, width: int, dtype) -> Optional[str]:
    """Why the TPU compiler refuses the kernel for this sketch (None when
    it compiles): ``kernel_refusal`` plus the 128-lane row rule."""
    why = kernel_refusal(width, dtype)
    if why is None and dim % LANES:
        why = (f"dim {dim} is not a multiple of {LANES} lanes, and a TPU "
               f"DMA cannot move a partial lane tile")
    return why


def rows_per_call(depth: int, n_tables: int, tile: int) -> int:
    """Most rows one ``pallas_call`` takes with ``n_tables`` prefetched
    (depth, rows) address tables — a multiple of ``tile``."""
    per_row = n_tables * (-(-depth // 8) * 8) * 4
    return max(tile, (_SMEM_BUDGET // per_row) // tile * tile)


def split_calls(k: int, limit: int, tile: int):
    """(rows per call, number of calls) covering ``k`` (a multiple of
    ``tile``) with calls of at most ``limit`` rows and the least padding."""
    n = -(-k // limit)
    per = -(-k // n)
    per = -(-per // tile) * tile
    return per, n


def col_vec(vals, n: int) -> jnp.ndarray:
    """(1, n) int32 vector of SMEM scalars ``vals`` (lane r = vals[r])."""
    c = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    acc = jnp.zeros((1, n), jnp.int32)
    for r, v in enumerate(vals):
        acc = jnp.where(c == r, v, acc)
    return acc


def row_vec(vals, n: int, rep: int = 1, dtype=jnp.int32) -> jnp.ndarray:
    """(n·rep, 1) vector whose sublane i holds ``vals[i // rep]``."""
    i = jax.lax.broadcasted_iota(jnp.int32, (n * rep, 1), 0) // rep
    acc = jnp.zeros((n * rep, 1), dtype)
    for r, v in enumerate(vals):
        acc = jnp.where(i == r, v, acc)
    return acc


class Groups:
    """Addressing of one hash row's buckets for one tile: which group each
    entry's bucket falls in, where, and the two one-hot matrices."""

    def __init__(self, buckets, tile: int, g: int):
        self.g = g
        self.tile = tile
        self.start = [pl.multiple_of((b // g) * g, g) for b in buckets]
        grp = [b // g for b in buckets]
        off = [b % g for b in buckets]
        tg = tile * g
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, tg), 1)
        sub = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        self.sel = ((lane // g == sub) & (lane % g == row_vec(off, tile))
                    ).astype(jnp.float32)                       # (tile, tg)
        s = jax.lax.broadcasted_iota(jnp.int32, (tg, 1), 0) % g
        self.place = ((row_vec(grp, tile, g) == col_vec(grp, tile))
                      & (s == col_vec(off, tile))
                      ).astype(jnp.float32)                     # (tg, tile)

    def read(self, groups: jnp.ndarray) -> jnp.ndarray:
        """(tile, dim) bucket rows out of the (tile·G, dim) groups."""
        return jax.lax.dot(self.sel, groups, precision=HIGHEST,
                           preferred_element_type=jnp.float32)

    def add(self, groups: jnp.ndarray, contrib: jnp.ndarray) -> jnp.ndarray:
        """Groups with every same-group (tile, dim) contribution added."""
        return groups + jax.lax.dot(self.place, contrib, precision=HIGHEST,
                                    preferred_element_type=jnp.float32)

    def row_ids(self) -> jnp.ndarray:
        """(tile·G, 1) uint32 sketch row of every group row."""
        s = jax.lax.broadcasted_iota(jnp.int32, (self.tile * self.g, 1), 0)
        base = row_vec(self.start, self.tile, self.g)
        return (base + s % self.g).astype(jnp.uint32)


def dma_groups(src, dst, j: int, groups: Groups, sem, *, to_hbm: bool):
    """Start the ``tile`` group DMAs of hash row ``j`` between the HBM
    sketch ``src``/``dst`` and the (depth, tile·G, dim) VMEM stage."""
    g = groups.g
    copies = []
    for r, start in enumerate(groups.start):
        hbm = src.at[j, pl.ds(start, g), :]
        vmem = dst.at[j, pl.ds(r * g, g), :]
        copies.append(pltpu.async_copy(vmem, hbm, sem) if to_hbm
                      else pltpu.async_copy(hbm, vmem, sem))
    return copies


def scan_calls(call, carry, xs, n_calls: int):
    """Run ``call(carry, x) -> (carry, y)`` over ``n_calls`` chunks: one
    direct call when there is one chunk, a ``lax.scan`` otherwise."""
    if n_calls == 1:
        carry, y = call(carry, jax.tree_util.tree_map(lambda a: a[0], xs))
        return carry, jax.tree_util.tree_map(lambda a: a[None], y)
    return jax.lax.scan(call, carry, xs)


def chunk_rows(a: jnp.ndarray, n_calls: int, axis: int) -> jnp.ndarray:
    """Split ``a``'s row axis ``axis`` into a leading (n_calls, ...) axis."""
    a = jnp.moveaxis(a, axis, 0)
    a = a.reshape((n_calls, a.shape[0] // n_calls) + a.shape[1:])
    return jnp.moveaxis(a, 1, axis + 1)
