"""Multi-head latent attention (MLA; DeepSeek-V2 §2.1, arXiv:2405.04434),
the training path, with ``q_lora_rank`` null (queries straight from x).

Per layer, x (b, s, d) in the compute dtype:

    q               = x W_q                     (h heads of nope + rope)
    [c_kv, k_rope]  = x W_kv_a                  (kv_lora_rank + rope)
    c_kv            = RMSNorm(c_kv)
    [k_nope, v]     = c_kv W_kv_b               (h heads of nope + v)
    q_rope, k_rope  ← RoPE; k_rope is one head, shared by all h
    o               = softmax(q·k / √(nope + rope), causal) v
    out             = o W_o

RoPE rotates halves (``common.apply_rope``); the published checkpoints
store the rotary columns interleaved, which is the same map up to a fixed
permutation of W_q's and W_kv_a's rotary columns."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import common as cm
from repro.models.config import ArchConfig


def mla_init(key, cfg: ArchConfig):
    d, h = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    ks = jax.random.split(key, 4)
    return {"wq": cm.dense_init(ks[0], d, h * (dn + dr)),
            "w_kv_a": cm.dense_init(ks[1], d, r + dr),
            "kv_norm": jnp.ones((r,), jnp.float32),
            "w_kv_b": cm.dense_init(ks[2], r, h * (dn + dv)),
            "wo": cm.dense_init(ks[3], h * dv, d)}


def mla_qkv(cfg: ArchConfig, p, x: jnp.ndarray, positions: jnp.ndarray):
    """q, k (b, s, h, nope + rope) and v (b, s, h, v_head_dim)."""
    b, s, _ = x.shape
    h, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = x.dtype
    q = (x @ p["wq"].astype(dt)).reshape(b, s, h, dn + dr)
    kv_a = x @ p["w_kv_a"].astype(dt)
    c_kv = cm.rmsnorm(kv_a[..., :r], p["kv_norm"], cfg.norm_eps)
    kv = (c_kv @ p["w_kv_b"].astype(dt)).reshape(b, s, h, dn + dv)
    q_rope = cm.apply_rope(q[..., dn:], positions, cfg.rope_theta)
    k_rope = cm.apply_rope(kv_a[:, :, None, r:], positions, cfg.rope_theta)
    q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (b, s, h, dr))], axis=-1)
    return q, k, kv[..., dn:]


def mla_apply(cfg: ArchConfig, p, x: jnp.ndarray,
              positions: jnp.ndarray) -> jnp.ndarray:
    """x (b, s, d) -> (b, s, d), causal."""
    b, s, _ = x.shape
    q, k, v = mla_qkv(cfg, p, x, positions)
    o = attn.flash_attention(q, k, v, True, cfg.attn_chunk)
    return o.reshape(b, s, -1) @ p["wo"].astype(x.dtype)
