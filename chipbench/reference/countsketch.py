"""The count-sketch of Charikar et al. as the optimizer state uses it.

A sketch of a (rows, dim) array is a (depth, width, dim) array S.  Row i
lands in bucket h_j(i) of hash row j, with sign s_j(i) for the signed
(count-sketch) estimator; the unsigned (count-min) estimator has no sign.
Estimates: the median over j of s_j(i)·S[j, h_j(i)] (signed), the min
over j of S[j, h_j(i)] (unsigned).

The hash family is 2-universal multiply-shift over uint32 with the
splitmix32 finalizer, its (a, b) pairs drawn from ``seed``: a sketch is
fully described by (seed, depth, width).  That is the state format the
checkpointed sketches have; it is written out here from that definition.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

_MIX1 = np.uint32(0x85EBCA6B)
_MIX2 = np.uint32(0xC2B2AE35)
_GOLDEN = np.uint32(0x9E3779B9)


def hash_params(seed: int, depth: int) -> np.ndarray:
    """(depth, 2) uint32 multiply-shift pairs (a odd, b) for ``seed``."""
    rng = np.random.RandomState(np.uint32(int(seed) ^ 0x5EED5EED))
    a = rng.randint(0, 2**31, size=depth, dtype=np.int64).astype(np.uint32)
    a = (a << np.uint32(1)) | np.uint32(1)
    b = rng.randint(0, 2**31, size=depth, dtype=np.int64).astype(np.uint32)
    return np.stack([a, b], axis=1)


def _mix(x):
    x = x ^ (x >> 16)
    x = x * _MIX1
    x = x ^ (x >> 13)
    x = x * _MIX2
    return x ^ (x >> 16)


def buckets(seed: int, depth: int, width: int, ids) -> jnp.ndarray:
    """h_j(ids): (k,) ints -> (depth, k) int32 in [0, width)."""
    p = hash_params(seed, depth)
    x = jnp.asarray(ids).astype(jnp.uint32)[None]
    h = _mix(x * jnp.asarray(p[:, :1]) + jnp.asarray(p[:, 1:2]))
    return (h % jnp.uint32(width)).astype(jnp.int32)


def signs(seed: int, depth: int, ids) -> jnp.ndarray:
    """s_j(ids): (k,) ints -> (depth, k) float32 in {+1, -1}."""
    p = hash_params(seed, depth)
    x = jnp.asarray(ids).astype(jnp.uint32)[None] + _GOLDEN
    h = _mix(x * jnp.asarray(p[:, 1:2]) + jnp.asarray(p[:, :1]))
    return jnp.where((h >> 31) == 0, 1.0, -1.0).astype(jnp.float32)
