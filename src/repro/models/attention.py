"""GQA attention: flash training path (a Pallas kernel on a TPU, a
custom-vjp scan elsewhere) + KV-cache serving path.

``flash_attention`` is the production path.  It picks one of two cores
from what it observes (``use_kernel``):

* **kernel** — causal self-attention on a TPU runs splash attention
  (``jax.experimental.pallas.ops.tpu.splash_attention``): each
  (query block, key block) tile of scores and probabilities lives in
  VMEM only, blocks wholly above the diagonal are skipped, and the
  forward, ``dq`` and ``dkv`` are the kernel's own, with (o, logsumexp)
  as residuals.  Operands go in as bf16 (q pre-scaled by 1/√hd in f32)
  and accumulate in f32: the products the scan's f32 einsums get from
  the MXU at the default precision, which rounds each operand to bf16.
* **scan** — ``_flash_core``, an online softmax over KV chunks with a
  ``custom_vjp`` whose forward saves only (o, logsumexp); the backward
  re-forms each chunk's probabilities instead of storing the (s × s)
  matrix (without it, a 4k-seq layer stores the full s² f32 attention
  matrix, ~4.5 GiB/device at the train_4k cells — EXPERIMENTS.md §Perf).
  It runs everywhere the kernel does not: off a TPU (the CPU), under a
  mesh GSPMD would split the call over (GSPMD does not partition a
  ``pallas_call``; ``_sharded_attention`` shards heads or the
  q-sequence over 'model'), at lengths no kernel block divides, for
  prefill continuation (``q_offset``, sq ≠ skv) and for non-causal or
  cross attention (``encdec.py``).  Its chunk is ``attn_chunk``.

``chunked_attention`` (plain scan, autodiff backward) is kept as the
reference oracle for tests.  Decode attends one query against the full
cache (scores are O(seq), no chunking needed).

Layouts:
  q, k     (b, s, hq|hkv, hd)      hq % hkv == 0 (GQA groups)
  v        (b, s, hkv, dv)         dv == hd, or not (latent attention)
  cache    (b, S_max, hkv, hd)     seq axis shardable over 'model' (SP)
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _group(q: jnp.ndarray, hkv: int) -> jnp.ndarray:
    b, s, hq, hd = q.shape
    return q.reshape(b, s, hkv, hq // hkv, hd)


def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      causal: bool, chunk: int = 1024,
                      q_offset: int = 0) -> jnp.ndarray:
    """Online-softmax attention over KV chunks.

    q, k (b,·,h,hd); v (b,skv,hkv,dv), where ``dv`` may differ from ``hd``
    (latent attention).  ``q_offset``: absolute position of q[0] relative
    to k[0] (prefill continuation).  Returns (b,sq,hq,dv)."""
    b, sq, hq, hd = q.shape
    skv, hkv, dv = k.shape[1], k.shape[2], v.shape[-1]
    g = hq // hkv
    chunk = min(chunk, skv)
    if skv % chunk != 0:
        chunk = skv  # odd lengths (tests, ragged tails): single chunk

    n_chunks = skv // chunk

    qg = _group(q, hkv).astype(jnp.float32) / jnp.sqrt(hd)  # (b,sq,hkv,g,hd)
    kc = k.reshape(b, n_chunks, chunk, hkv, hd)
    vc = v.reshape(b, n_chunks, chunk, hkv, dv)
    q_pos = q_offset + jnp.arange(sq)

    def body(carry, xs):
        m, l, acc = carry                       # (b,hkv,g,sq), ..., (...,dv)
        kb, vb, c_idx = xs                      # (b,chunk,hkv,hd) ×2, ()
        s = jnp.einsum("bqhgd,bchd->bhgqc", qg, kb.astype(jnp.float32))
        if causal:
            k_pos = c_idx * chunk + jnp.arange(chunk)
            mask = q_pos[:, None] >= k_pos[None, :]         # (sq, chunk)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqc,bchd->bhgqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    init = (jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32),
            jnp.zeros((b, hkv, g, sq), jnp.float32),
            jnp.zeros((b, hkv, g, sq, dv), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(
        body, init,
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0),
         jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]            # (b,hkv,g,sq,dv)
    out = jnp.moveaxis(out, 3, 1).reshape(b, sq, hq, dv)
    return out.astype(q.dtype)


def decode_attention(q: jnp.ndarray, cache_k: jnp.ndarray,
                     cache_v: jnp.ndarray, length: jnp.ndarray) -> jnp.ndarray:
    """One-token attention against the cache.

    q (b,1,hq,hd); cache_k/v (b,S,hkv,hd); length () or (b,) valid prefix.
    The seq axis of the cache may be sharded ('model'); the max/sum
    reductions below become cross-shard collectives (flash-decoding)."""
    b, _, hq, hd = q.shape
    S, hkv = cache_k.shape[1], cache_k.shape[2]
    qg = _group(q, hkv).astype(jnp.float32) / jnp.sqrt(hd)  # (b,1,hkv,g,hd)
    s = jnp.einsum("bqhgd,bshd->bhgqs", qg, cache_k.astype(jnp.float32))
    pos = jnp.arange(S)
    valid = pos[None] < jnp.broadcast_to(jnp.asarray(length), (b,))[:, None]
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqs,bshd->bhgqd", p, cache_v.astype(jnp.float32))
    out = jnp.moveaxis(out, 3, 1).reshape(b, 1, hq, hd)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Flash attention (custom VJP: backward recomputes chunk probabilities)
# ---------------------------------------------------------------------------

def _flash_fwd_scan(qg, k, v, *, causal: bool, chunk: int, q_offset: int):
    """qg (b,sq,hkv,g,hd) pre-scaled fp32; k (b,skv,hkv,hd); v
    (b,skv,hkv,dv).  Returns (out (b,hkv,g,sq,dv) fp32, lse (b,hkv,g,sq))."""
    b, sq, hkv, g, hd = qg.shape
    skv, dv = k.shape[1], v.shape[-1]
    n_chunks = skv // chunk
    kc = jnp.moveaxis(k.reshape(b, n_chunks, chunk, hkv, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, n_chunks, chunk, hkv, dv), 1, 0)
    q_pos = q_offset + jnp.arange(sq)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, c_idx = xs
        s = jnp.einsum("bqhgd,bchd->bhgqc", qg, kb.astype(jnp.float32))
        if causal:
            k_pos = c_idx * chunk + jnp.arange(chunk)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqc,bchd->bhgqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    init = (jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32),
            jnp.zeros((b, hkv, g, sq), jnp.float32),
            jnp.zeros((b, hkv, g, sq, dv), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, init,
                                  (kc, vc, jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_core(q, k, v, causal: bool, chunk: int, q_offset: int):
    b, sq, hq, hd = q.shape
    hkv, dv = k.shape[2], v.shape[-1]
    qg = _group(q, hkv).astype(jnp.float32) / jnp.sqrt(hd)
    out, _ = _flash_fwd_scan(qg, k, v, causal=causal, chunk=chunk,
                             q_offset=q_offset)
    out = jnp.moveaxis(out, 3, 1).reshape(b, sq, hq, dv)
    return out.astype(q.dtype)


def flash_attention(q, k, v, causal: bool = True, chunk: int = 1024,
                    q_offset: int = 0):
    """Memory-linear attention.  q (b,sq,hq,hd); k (b,skv,hkv,hd); v
    (b,skv,hkv,dv), ``dv`` free (latent attention: 192 against 128); the
    scale is 1/√hd.  Returns (b,sq,hq,dv).  On a TPU, causal
    self-attention runs the Pallas kernel (``use_kernel``); elsewhere the
    scan, which matches ``chunked_attention`` to fp32 accumulation
    accuracy; ragged sequence lengths fall back to the reference path."""
    from repro.distributed import sharding
    mesh = sharding.current_mesh()
    if use_kernel(platform=jax.default_backend(), causal=causal,
                  sq=q.shape[1], skv=k.shape[1], q_offset=q_offset,
                  hq=q.shape[2], hkv=k.shape[2],
                  mesh=None if mesh is None else
                  dict(zip(mesh.axis_names, mesh.devices.shape)),
                  manual=sharding.in_manual_region()):
        return kernel_attention(q, k, v, block=kernel_block(q.shape[1]))
    skv = k.shape[1]
    chunk = min(chunk, skv)
    if skv % chunk != 0:
        return chunked_attention(q, k, v, causal=causal, chunk=chunk,
                                 q_offset=q_offset)
    return _flash_core(q, k, v, causal, chunk, q_offset)


# ---------------------------------------------------------------------------
# The Pallas kernel path (splash attention, TPU)
# ---------------------------------------------------------------------------

KERNEL_BLOCKS = (1024, 512, 256, 128)    # largest first; 128: the lane width


def kernel_block(s: int) -> Optional[int]:
    """The kernel's query and key block for sequence length ``s``: the
    largest of ``KERNEL_BLOCKS`` that divides it, or None."""
    return next((blk for blk in KERNEL_BLOCKS if s % blk == 0), None)


def use_kernel(*, platform: str, causal: bool, sq: int, skv: int,
               q_offset: int, hq: int, hkv: int,
               mesh: Optional[Mapping[str, int]] = None,
               manual: bool = False) -> bool:
    """Whether ``flash_attention`` runs the Pallas kernel: causal
    self-attention (sq == skv, no offset) on a TPU, at a length a kernel
    block divides, with whole GQA groups, where GSPMD would not split the
    call.  ``mesh`` is the active mesh's axis sizes (None: no mesh);
    ``manual``: traced inside a ``shard_map`` body, where every array is
    already one device's."""
    ways = 1 if mesh is None or manual else math.prod(mesh.values())
    return (platform == "tpu" and causal and sq == skv and q_offset == 0
            and kernel_block(sq) is not None and hq % hkv == 0
            and ways == 1)


@functools.lru_cache(maxsize=None)
def _splash_kernel(heads: int, s: int, block: int, interpret: bool):
    """Splash attention over ``heads`` heads of length ``s`` with a causal
    mask, built once per shape; fully masked blocks are skipped."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    mask = sm.MultiHeadMask([sm.CausalMask((s, s))] * heads)
    blocks = sk.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block)
    with jax.ensure_compile_time_eval():   # mask tables: constants
        return sk.make_splash_mha(mask, block_sizes=blocks, head_shards=1,
                                  q_seq_shards=1, interpret=interpret)


def kernel_attention(q, k, v, *, block: int, interpret: bool = False):
    """Causal self-attention through the splash kernel, vmapped over the
    batch.  q, k (b,s,hq|hkv,hd); v (b,s,hkv,dv); the scale is 1/√hd,
    applied to q in f32 before the bf16 cast.  Returns (b,s,hq,dv) in
    q's dtype; gradients flow through the kernel's own backward."""
    b, s, hq, hd = q.shape
    qs = (q.astype(jnp.float32) / jnp.sqrt(hd)).astype(jnp.bfloat16)
    heads_first = lambda x: jnp.swapaxes(x.astype(jnp.bfloat16), 1, 2)
    kernel = _splash_kernel(hq, s, block, interpret)
    o = jax.vmap(kernel)(heads_first(qs), heads_first(k), heads_first(v))
    return jnp.swapaxes(o, 1, 2).astype(q.dtype)


def _flash_fwd(q, k, v, causal, chunk, q_offset):
    b, sq, hq, hd = q.shape
    hkv, dv = k.shape[2], v.shape[-1]
    qg = _group(q, hkv).astype(jnp.float32) / jnp.sqrt(hd)
    o, lse = _flash_fwd_scan(qg, k, v, causal=causal, chunk=chunk,
                             q_offset=q_offset)
    out = jnp.moveaxis(o, 3, 1).reshape(b, sq, hq, dv).astype(q.dtype)
    return out, (qg, k, v, o, lse)


def _flash_bwd(causal, chunk, q_offset, res, dout):
    qg, k, v, o, lse = res
    qdt = v.dtype
    b, sq, hkv, g, hd = qg.shape
    skv, dv = k.shape[1], v.shape[-1]
    n_chunks = skv // chunk
    do = jnp.moveaxis(
        dout.astype(jnp.float32).reshape(b, sq, hkv, g, dv), 1, 3)
    # D_q = rowsum(do ⊙ o)
    D = jnp.sum(do * o, axis=-1)                      # (b,hkv,g,sq)
    kc = jnp.moveaxis(k.reshape(b, n_chunks, chunk, hkv, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, n_chunks, chunk, hkv, dv), 1, 0)
    q_pos = q_offset + jnp.arange(sq)

    def body(dq, xs):
        kb, vb, c_idx = xs
        s = jnp.einsum("bqhgd,bchd->bhgqc", qg, kb.astype(jnp.float32))
        if causal:
            k_pos = c_idx * chunk + jnp.arange(chunk)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])               # (b,hkv,g,sq,c)
        dv_c = jnp.einsum("bhgqc,bhgqd->bchd", p, do)
        dp = jnp.einsum("bhgqd,bchd->bhgqc", do, vb.astype(jnp.float32))
        ds = p * (dp - D[..., None])
        dq = dq + jnp.einsum("bhgqc,bchd->bqhgd", ds, kb.astype(jnp.float32))
        dk_c = jnp.einsum("bhgqc,bqhgd->bchd", ds, qg)
        return dq, (dk_c, dv_c)

    dq0 = jnp.zeros((b, sq, hkv, g, hd), jnp.float32)
    dq, (dk_st, dv_st) = jax.lax.scan(body, dq0,
                                      (kc, vc, jnp.arange(n_chunks)))
    scale = 1.0 / jnp.sqrt(hd)
    dq = (dq * scale).reshape(b, sq, hkv * g, hd).astype(qdt)
    dk = jnp.moveaxis(dk_st, 0, 1).reshape(b, skv, hkv, hd).astype(k.dtype)
    dv_ = jnp.moveaxis(dv_st, 0, 1).reshape(b, skv, hkv, dv).astype(v.dtype)
    return dq, dk, dv_


_flash_core.defvjp(_flash_fwd, _flash_bwd)


@dataclasses.dataclass
class AttnParams:
    """Just a namespace helper — attention params live in plain dicts."""


def attn_init(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
              qkv_bias: bool = False):
    ks = jax.random.split(key, 4)
    import repro.models.common as cm
    p = {
        "wq": cm.dense_init(ks[0], d_model, n_heads * head_dim),
        "wk": cm.dense_init(ks[1], d_model, n_kv * head_dim),
        "wv": cm.dense_init(ks[2], d_model, n_kv * head_dim),
        "wo": cm.dense_init(ks[3], n_heads * head_dim, d_model),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), jnp.float32)
        p["bk"] = jnp.zeros((n_kv * head_dim,), jnp.float32)
        p["bv"] = jnp.zeros((n_kv * head_dim,), jnp.float32)
    return p


def attn_qkv(p, x: jnp.ndarray, n_heads: int, n_kv: int, head_dim: int
             ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    b, s, _ = x.shape
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    return (q.reshape(b, s, n_heads, head_dim),
            k.reshape(b, s, n_kv, head_dim),
            v.reshape(b, s, n_kv, head_dim))


def attn_out(p, o: jnp.ndarray) -> jnp.ndarray:
    b, s, h, hd = o.shape
    return o.reshape(b, s, h * hd) @ p["wo"].astype(o.dtype)
