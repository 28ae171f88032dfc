"""Weights and tables made from ``--seed``, on the device, by the benchmark.

Every array is drawn in blocks of leading-axis rows, block ``i`` from
``fold_in(key, i)``, so that one block can be drawn again on its own: the
correctness check measures how far a table moved from its initial value
without holding a second copy of it, and the reference starts from the
same values without taking them from the program."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BLOCK_ELEMENTS = 1 << 23      # 32 MiB of float32 per block at most


def seed_key(seed: int, stream: int):
    """A key for ``stream`` of the run seeded by ``seed`` (any size: the
    high bits are folded in, not dropped)."""
    key = jax.random.key(int(seed) & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (int(seed) >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


def block_rows(shape) -> int:
    """Rows of the leading axis per block: the largest divisor of
    ``shape[0]`` that keeps a block within ``BLOCK_ELEMENTS``."""
    rows = int(shape[0])
    per_row = 1
    for s in shape[1:]:
        per_row *= int(s)
    cap = max(1, BLOCK_ELEMENTS // max(per_row, 1))
    for b in range(min(rows, cap), 0, -1):
        if rows % b == 0:
            return b
    return 1


def _block(key, i, bshape, scale):
    x = jax.random.normal(jax.random.fold_in(key, i), bshape, jnp.float32)
    return x * scale


@functools.partial(jax.jit, static_argnames=("shape", "scale"))
def draw(key, *, shape, scale=1.0):
    """The float32 array ``shape``, normal × ``scale``."""
    b = block_rows(shape)
    bshape = (b,) + tuple(shape[1:])
    blocks = jax.lax.map(lambda i: _block(key, i, bshape, scale),
                         jnp.arange(shape[0] // b))
    return blocks.reshape(shape)


@functools.partial(jax.jit, static_argnames=("scale",))
def change_sq(x, key, *, scale=1.0):
    """Σ (x − x₀)² in float32, with x₀ the array ``draw`` made for ``key``,
    drawn again block by block."""
    b = block_rows(x.shape)
    bshape = (b,) + tuple(x.shape[1:])
    start = (0,) * (x.ndim - 1)

    def body(i, acc):
        blk = jax.lax.dynamic_slice(x, (i * b,) + start, bshape)
        d = blk.astype(jnp.float32) - _block(key, i, bshape, scale)
        return acc + jnp.sum(d * d)

    return jax.lax.fori_loop(0, x.shape[0] // b, body,
                             jnp.zeros((), jnp.float32))
