"""Pallas-TPU kernels for the count-sketch hot path.

  cs_adam_tiled.py — fused TILED Adam: TILE deduplicated rows per grid step,
                     double-buffered grad/update pipeline (DESIGN.md §10)
  cs_ema_tiled.py  — fused TILED update_read: one moment's query→Δ→scatter
                     in a single pass — the AuxStore protocol's dense-path
                     op (DESIGN.md §14)
  dedup.py         — sort + segment-sum pre-pass that turns an (ids, rows)
                     batch collision-free so the tiled kernel applies
  row_groups.py    — the aligned row-group DMA + one-hot select/place
                     machinery both tiled kernels share, and the rules
                     for what the TPU compiler accepts
  ops.py           — the registered implementations, one per backend name
  ref.py           — pure-jnp oracles: the 'ref' pair backend and the
                     tiled kernel's per-tile reference in the tests
  registry.py      — the shared (store kind, op) → {backend: fn} registry

Backend registry
----------------
Interchangeable implementations ("backends") are selected by name through
``registry.lookup(kind, op, backend)`` — reachable from
``SketchHParams.backend``, the ``backend=`` field on sketch-backed
``AuxStore`` dataclasses (rides in StoreTrees, plans, and checkpoint
manifests), ``launch/train.py --store-backend``, and the benchmarks.

('pair', 'adam_rows') — the fused sparse-rows CS-Adam step:

  ref        pure-jnp ``lax.scan`` per-item oracle (exact paper semantics)
  xla        dedup pre-pass + the vectorized jnp batch step — no Pallas;
             same semantics as ``tiled`` with one whole-batch tile (the
             default off-TPU)
  tiled      dedup pre-pass + ``cs_adam_tiled`` — TILE rows per grid step;
             identical to ``ref`` on collision-free batches, within
             median/min-noise tolerance otherwise (the TPU fast path:
             f32 cells, dim a multiple of 128)
  interpret  ``tiled`` under the Pallas interpreter — runs the kernel
             body on any host; chosen only by name (tests)

('sketch' | 'countmin', 'update_read') — the dense-path fused one-pass
EMA op of the ``AuxStore`` protocol (DESIGN.md §14):

  ref        composed primitives one-shot (query → ema_delta → update);
             bit-identical to the composed fallback
  xla        one fused gather/Δ/scatter pass, addressing hashed once (and
             host-cached for the dense arange(n) row set) — bit-identical
             to ``ref``
  tiled      the ``cs_ema_tiled`` Pallas kernel (TPU fast path: f32 or
             bf16 cells, dim a multiple of 128)
  interpret  ``tiled`` under the Pallas interpreter (by name only)

('sketch' | 'countmin', 'update_slab' | 'gather_slab') — the shard-local
halves of the sharded optimizer body (DESIGN.md §17): masked scatter-add
into / gather out of one shard's (depth, local_width, dim) slab.

  ref        the vmapped forms in ``core.sketch`` (semantics definition)
  xla        depth-unrolled flat gathers/scatters — bit-identical to
             ``ref``, the fast path everywhere (no tiled variant: the
             slab ops run under shard_map where Pallas grids don't
             compose yet, so 'auto' resolves to 'xla' on every host)

Each op runs one implementation per platform — ``tiled`` on a TPU,
``xla`` elsewhere (``xla`` everywhere for the slab ops) — besides the
``ref`` oracle and, for the two kernels, the ``interpret`` backend the
tests need.  ``registry.resolve(kind, op, None|'auto')`` picks
``tiled`` on TPU and ``xla`` elsewhere; given the op's sketch specs (as
``adam_rows``/``update_read`` do) it picks ``xla`` for a sketch the
kernel refuses (int8 cells, dim not a multiple of 128, bf16 in the pair
op), and an explicit backend name that refuses a sketch — or that is not
registered — raises.  New backends (e.g. a GPU port) attach via
``registry.register``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax.numpy as jnp

from repro.kernels import dedup, ops, ref, registry  # noqa: F401
from repro.kernels import row_groups as rg


def adam_rows(spec_m, spec_v, M, V, ids, g, step, *,
              lr, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
              backend: Optional[str] = None):
    """Sparse-rows CS-Adam through the named backend (None/'auto' = best).

    Returns ``(M', V', row_updates)`` with ``row_updates`` aligned to the
    input ``ids`` such that ``params.at[ids].add(row_updates)`` is the
    correct application under every backend (the tiled backend zeros
    duplicate occurrences after the first; see ``dedup.scatter_back``).
    """
    name = registry.resolve("pair", "adam_rows", backend,
                            specs=(spec_m, spec_v))
    fn = registry.lookup("pair", "adam_rows", name)
    return fn(spec_m, spec_v, M, V, ids, g, step,
              lr=lr, b1=b1, b2=b2, eps=eps)


def update_read(spec, S, ids, delta, *, beta: float, scale: float,
                mask=None, backend: Optional[str] = None, sr_seed=None):
    """One fused EMA step on one sketch tensor: ``(S', est)`` such that
    row content moves to ``β·content + scale·delta`` at ``ids`` and
    ``est`` is the post-step estimate (batch semantics) — the kernel half
    of ``AuxStore.update_read`` (DESIGN.md §14).  Dispatches on the
    store kind ('sketch' for signed specs, 'countmin' otherwise) through
    the registry.

    ``sr_seed`` (uint32, from ``quantize.step_seed(spec.seed, step)``)
    keys the stochastic-rounding bits for low-precision cells; f32
    sketches ignore it.  None pins the step-0 stream — callers in a
    training loop MUST thread the step so successive writes draw fresh
    rounding bits (DESIGN.md §18)."""
    kind = "sketch" if spec.signed else "countmin"
    name = registry.resolve(kind, "update_read", backend, specs=(spec,))
    fn = registry.lookup(kind, "update_read", name)
    return fn(spec, S, ids, delta, beta=beta, scale=scale, mask=mask,
              sr_seed=sr_seed)


def update_slab(spec, slab, ids, delta, shard, *,
                backend: Optional[str] = None):
    """Scatter ``delta`` rows into ONE shard's (depth, local_width, dim)
    slab — ids hashing outside the slab are dropped, so the per-shard
    results concatenate to the full-width ``sketch.update`` exactly.
    None/'auto' — and backends with no slab variant (e.g. a store pinned
    to 'tiled' for its dense path) — resolve to 'xla' (see module
    docstring)."""
    kind = "sketch" if spec.signed else "countmin"
    if backend in (None, "auto") \
            or backend not in registry.backends(kind, "update_slab"):
        backend = "xla"
    name = registry.resolve(kind, "update_slab", backend, specs=(spec,))
    fn = registry.lookup(kind, "update_slab", name)
    return fn(spec, slab, ids, delta, shard)


def gather_slab(spec, slab, ids, shard, *, backend: Optional[str] = None):
    """This shard's (depth, k, dim) query contributions (zeros off-slab);
    psum over the shard axis then ``sketch.finish_query`` reproduces the
    full-width ``sketch.query`` exactly.  None/'auto' (and slab-less
    backends) resolve to 'xla'."""
    kind = "sketch" if spec.signed else "countmin"
    if backend in (None, "auto") \
            or backend not in registry.backends(kind, "gather_slab"):
        backend = "xla"
    name = registry.resolve(kind, "gather_slab", backend, specs=(spec,))
    fn = registry.lookup(kind, "gather_slab", name)
    return fn(spec, slab, ids, shard)


def _f32_only(spec) -> Optional[str]:
    if jnp.dtype(spec.dtype) != jnp.float32:
        return (f"{jnp.dtype(spec.dtype).name} cells "
                "(this kernel keeps float32)")
    return None


def _pair_kernel(spec) -> Optional[str]:
    return _f32_only(spec) or rg.kernel_refusal(spec.width, spec.dtype)


def _pair_tiled(spec) -> Optional[str]:
    return _f32_only(spec) or rg.tiled_refusal(spec.dim, spec.width,
                                               spec.dtype)


def _ema_kernel(spec) -> Optional[str]:
    return rg.kernel_refusal(spec.width, spec.dtype)


def _ema_tiled(spec) -> Optional[str]:
    return rg.tiled_refusal(spec.dim, spec.width, spec.dtype)


registry.register("pair", "adam_rows", "ref", ops.adam_rows_ref)
registry.register("pair", "adam_rows", "xla", ops.adam_rows_xla)
registry.register("pair", "adam_rows", "tiled", ops.adam_rows_tiled,
                  refusal=_pair_tiled)
registry.register("pair", "adam_rows", "interpret",
                  functools.partial(ops.adam_rows_tiled, interpret=True),
                  refusal=_pair_kernel)

for _kind in ("sketch", "countmin"):
    registry.register(_kind, "update_read", "ref", ops.ema_update_read_ref)
    registry.register(_kind, "update_read", "xla", ops.ema_update_read_xla)
    registry.register(_kind, "update_read", "tiled",
                      ops.ema_update_read_tiled, refusal=_ema_tiled)
    registry.register(_kind, "update_read", "interpret",
                      functools.partial(ops.ema_update_read_tiled,
                                        interpret=True),
                      refusal=_ema_kernel)
    registry.register(_kind, "update_slab", "ref", ops.cs.update_slab)
    registry.register(_kind, "update_slab", "xla", ops.slab_update_xla)
    registry.register(_kind, "gather_slab", "ref", ops.cs.gather_slab)
    registry.register(_kind, "gather_slab", "xla", ops.slab_gather_xla)
del _kind
