"""Decoder-only LM family: dense GQA (internlm2 / yi / granite / qwen2) and
MoE variants (qwen2-moe / llama4-maverick).  Also the text backbone reused
by the VLM.

All stacks are a single ``lax.scan`` over stacked layer params; training
wraps the body in ``jax.checkpoint`` (remat).  ``moe_every == 2``
(llama4-maverick) interleaves dense-FFN and MoE layers: the scan unit
becomes a [dense, moe] *block* so the stack stays a single homogeneous
scan (compile economy, DESIGN.md §7).  ``first_k_dense`` leading dense
layers (DeepSeek-style) are a scan of their own before the MoE layers'.
Latent attention (``cfg.mla``) runs ``models/mla.py`` in place of GQA, on
the training path only.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import common as cm
from repro.models import mla
from repro.models import moe as moe_lib
from repro.models.config import ArchConfig
from repro.obs.profiling import scope

Params = Dict[str, Any]


def uses_blocks(cfg: ArchConfig) -> bool:
    return cfg.family == "moe" and cfg.moe_every > 1


def _dense_cfg(cfg: ArchConfig) -> ArchConfig:
    """The interleaved dense layer's view of the config."""
    return dataclasses.replace(cfg, family="gqa",
                               d_ff=cfg.dense_d_ff or cfg.d_ff)


def layer_groups(cfg: ArchConfig):
    """The stacks of ``params['layers']`` in order: (key or None for an
    unkeyed stack, the stack's config, its depth)."""
    if cfg.first_k_dense:
        return [("dense", _dense_cfg(cfg), cfg.first_k_dense),
                ("moe", cfg, cfg.n_layers - cfg.first_k_dense)]
    return [(None, cfg, n_scan_units(cfg))]


def n_scan_units(cfg: ArchConfig) -> int:
    if uses_blocks(cfg):
        assert cfg.moe_every == 2, "only moe_every in (1, 2) is implemented"
        assert cfg.n_layers % 2 == 0
        return cfg.n_layers // 2
    return cfg.n_layers


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def _ffn_init(key, cfg: ArchConfig):
    if cfg.family == "moe":
        return moe_lib.moe_init(key, cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": cm.dense_init(k1, cfg.d_model, cfg.d_ff),
            "w_up": cm.dense_init(k2, cfg.d_model, cfg.d_ff),
            "w_down": cm.dense_init(k3, cfg.d_ff, cfg.d_model)}


def _ffn_apply(cfg: ArchConfig, p, x: jnp.ndarray):
    """x: (b, s, d) -> (y, aux_loss, stats): the routing counts of a
    sigmoid-routed layer (``moe.moe_apply_held``), else empty."""
    if cfg.family == "moe" and cfg.router == "sigmoid":
        return moe_lib.moe_apply_held(cfg, p, x)
    if cfg.family == "moe":
        b, s, d = x.shape
        y, aux = moe_lib.moe_apply(cfg, p, x.reshape(b * s, d))
        return y.reshape(b, s, d), aux, {}
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    dt = x.dtype
    h = act(x @ p["w_gate"].astype(dt)) * (x @ p["w_up"].astype(dt))
    h = cm.shard_act(h, None, "model")
    return h @ p["w_down"].astype(dt), jnp.zeros((), jnp.float32), {}


def layer_init(key, cfg: ArchConfig):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), jnp.float32),
        "attn": mla.mla_init(k1, cfg) if cfg.mla else
        attn.attn_init(k1, cfg.d_model, cfg.n_heads, cfg.n_kv,
                       cfg.head_dim, cfg.qkv_bias),
        "ln2": jnp.ones((cfg.d_model,), jnp.float32),
        "ffn": _ffn_init(k2, cfg),
    }


def _attn_mode(cfg: ArchConfig) -> str:
    """'heads' (TP over heads) or 'qseq' (q-sequence sharding fallback)."""
    from repro.distributed.sharding import current_mesh
    mesh = current_mesh()
    model = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1) \
        if mesh is not None else 1
    heads = cfg.n_heads if not cfg.repeat_kv else cfg.n_heads
    return "heads" if heads % model == 0 else "qseq"


def _sharded_attention(cfg: ArchConfig, q, k, v):
    """TP-constrained flash attention.

    * ``cfg.repeat_kv``: KV heads replicated up to hq (Megatron GQA-on-TP
      — hq divides the model axis but hkv doesn't), einsums head-local.
    * heads divide the model axis → shard the head dim;
    * otherwise (qwen2's 14H, llama4's 40H) → shard the *q-sequence* dim
      over 'model' and replicate K/V: causal attention is independent per
      query position, so scores shrink by the TP degree instead of being
      replicated at full head count (a 10.7 GiB/chunk f32 buffer at the
      llama4 train cell — measured, EXPERIMENTS.md §Perf)."""
    if cfg.repeat_kv and cfg.n_heads != cfg.n_kv:
        g = cfg.n_heads // cfg.n_kv
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    if _attn_mode(cfg) == "heads":
        q = cm.shard_act(q, None, "model", None)
        k = cm.shard_act(k, None, "model", None)
        v = cm.shard_act(v, None, "model", None)
        o = attn.flash_attention(q, k, v, True, cfg.attn_chunk)
        return cm.shard_act(o, None, "model", None)
    q = cm.shard_act(q, "model", None, None)
    k = cm.shard_act(k, None, None, None)
    v = cm.shard_act(v, None, None, None)
    o = attn.flash_attention(q, k, v, True, cfg.attn_chunk)
    return cm.shard_act(o, "model", None, None)


def layer_apply_train(cfg: ArchConfig, p, x: jnp.ndarray,
                      positions: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    x, aux, _ = _layer_train(cfg, p, x, positions)
    return x, aux


def _layer_train(cfg: ArchConfig, p, x: jnp.ndarray, positions: jnp.ndarray):
    """One layer: (x, aux loss, routing stats)."""
    if cfg.mla:
        with scope("obs.attn"):
            h = cm.rmsnorm(x, p["ln1"], cfg.norm_eps)
            x = x + mla.mla_apply(cfg, p["attn"], h, positions)
        h = cm.rmsnorm(x, p["ln2"], cfg.norm_eps)
        f, aux, stats = _ffn_apply(cfg, p["ffn"], h)
        return x + f, aux, stats
    # gather boundary pinned at the bf16 post-norm tensor — but ONLY for
    # head-sharded attention; q-seq-sharded archs (llama4/qwen2) keep the
    # residual seq-sharded straight into the q projection (§Perf L2)
    h = cm.rmsnorm(x, p["ln1"], cfg.norm_eps)
    h = cm.shard_act(h, None, None) if _attn_mode(cfg) == "heads" \
        else cm.shard_act(h, "model", None)
    q, k, v = attn.attn_qkv(p["attn"], h, cfg.n_heads, cfg.n_kv, cfg.head_dim)
    q = cm.apply_rope(q, positions, cfg.rope_theta)
    k = cm.apply_rope(k, positions, cfg.rope_theta)
    o = _sharded_attention(cfg, q, k, v)
    # row-parallel outputs constrained seq-sharded BEFORE the residual
    # add => reduce-scatter instead of all-reduce (§Perf)
    x = x + cm.shard_act(attn.attn_out(p["attn"], o), "model", None)
    h = cm.shard_act(cm.rmsnorm(x, p["ln2"], cfg.norm_eps), None, None)
    f, aux, stats = _ffn_apply(cfg, p["ffn"], h)
    return x + cm.shard_act(f, "model", None), aux, stats


def layer_prefill(cfg: ArchConfig, p, x: jnp.ndarray, positions: jnp.ndarray):
    """Like train but returns the (k, v) cache for this layer."""
    h = cm.rmsnorm(x, p["ln1"])
    h = cm.shard_act(h, None, None) if _attn_mode(cfg) == "heads" \
        else cm.shard_act(h, "model", None)
    q, k, v = attn.attn_qkv(p["attn"], h, cfg.n_heads, cfg.n_kv, cfg.head_dim)
    q = cm.apply_rope(q, positions, cfg.rope_theta)
    k = cm.apply_rope(k, positions, cfg.rope_theta)
    o = _sharded_attention(cfg, q, k, v)
    x = x + cm.shard_act(attn.attn_out(p["attn"], o), "model", None)
    h = cm.shard_act(cm.rmsnorm(x, p["ln2"]), None, None)
    f = _ffn_apply(cfg, p["ffn"], h)[0]
    return x + cm.shard_act(f, "model", None), (k, v)


def layer_decode(cfg: ArchConfig, p, x: jnp.ndarray, ck: jnp.ndarray,
                 cv: jnp.ndarray, pos: jnp.ndarray):
    """x (b,1,d); ck/cv (b,S,hkv,hd); pos () current length."""
    h = cm.rmsnorm(x, p["ln1"])
    q, k, v = attn.attn_qkv(p["attn"], h, cfg.n_heads, cfg.n_kv, cfg.head_dim)
    posv = jnp.full((x.shape[0], 1), pos, jnp.int32)
    q = cm.apply_rope(q, posv, cfg.rope_theta)
    k = cm.apply_rope(k, posv, cfg.rope_theta)
    ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, pos, 0, 0))
    cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, pos, 0, 0))
    o = attn.decode_attention(q, ck, cv, pos + 1)
    x = x + attn.attn_out(p["attn"], o)
    h = cm.rmsnorm(x, p["ln2"])
    f = _ffn_apply(cfg, p["ffn"], h)[0]
    return x + f, ck, cv


def _no_serving(cfg: ArchConfig):
    if cfg.mla or cfg.first_k_dense or cfg.router != "softmax":
        raise NotImplementedError(
            f"{cfg.name}: latent attention, leading dense layers and the "
            f"sigmoid router have a training path only")


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------

def init(key, cfg: ArchConfig) -> Params:
    ke, kl, kh = jax.random.split(key, 3)
    if cfg.first_k_dense:
        layers = {}
        for (name, gcfg, n), k in zip(layer_groups(cfg),
                                      jax.random.split(kl)):
            layers[name] = jax.vmap(lambda k, c=gcfg: layer_init(k, c))(
                jax.random.split(k, n))
    elif uses_blocks(cfg):
        nb = n_scan_units(cfg)
        ka, kb = jax.random.split(kl)
        dcfg = _dense_cfg(cfg)
        layers = {
            "dense": jax.vmap(lambda k: layer_init(k, dcfg))(
                jax.random.split(ka, nb)),
            "moe": jax.vmap(lambda k: layer_init(k, cfg))(
                jax.random.split(kb, nb)),
        }
    else:
        layers = jax.vmap(lambda k: layer_init(k, cfg))(
            jax.random.split(kl, cfg.n_layers))
    p = {
        "tok_embed": {"table": cm.embed_init(ke, cfg.vocab, cfg.d_model)},
        "layers": layers,
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        "lm_head": {"table": cm.embed_init(kh, cfg.vocab, cfg.d_model)},
    }
    return p


def backbone_train(cfg: ArchConfig, params: Params, x: jnp.ndarray,
                   positions: jnp.ndarray, remat: bool = True):
    """Run the layer stack; x (b,s,d).  Returns (x, total_aux_loss)."""
    x, aux, _ = backbone_train_stats(cfg, params, x, positions, remat)
    return x, aux


def backbone_train_stats(cfg: ArchConfig, params: Params, x: jnp.ndarray,
                         positions: jnp.ndarray, remat: bool = True):
    """``backbone_train`` that also returns the routing stats of the
    sigmoid-routed layers: ``loads`` (MoE layers, E) and ``routed_here``
    (summed over layers); empty for other configs."""
    if uses_blocks(cfg):
        x, aux = _scan_blocks(cfg, params, x, positions, remat)
        return x, aux, {}
    layers = params["layers"]
    aux = jnp.zeros((), jnp.float32)
    stats = {}
    for name, gcfg, _ in layer_groups(cfg):
        x, a, st = _scan_layers(gcfg, layers if name is None
                                else layers[name], x, positions, remat)
        aux = aux + a
        stats.update(st)
    if "routed_here" in stats:
        stats["routed_here"] = jnp.sum(stats["routed_here"])
    return x, aux, stats


def _scan_layers(cfg: ArchConfig, layers, x, positions, remat: bool):
    def body(carry, lp):
        h, aux = carry
        h, a, st = _layer_train(cfg, lp, h, positions)
        return (h, aux + a), st

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (x, aux), stats = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   layers)
    return x, aux, stats


def _scan_blocks(cfg: ArchConfig, params: Params, x: jnp.ndarray,
                 positions: jnp.ndarray, remat: bool):
    """The [dense, moe] block scan of ``moe_every == 2``."""
    dcfg = _dense_cfg(cfg)

    def body(carry, lp):
        h, aux = carry
        h, a1 = layer_apply_train(dcfg, lp["dense"], h, positions)
        h, a2 = layer_apply_train(cfg, lp["moe"], h, positions)
        return (h, aux + a1 + a2), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               params["layers"])
    return x, aux


def with_router_bias(cfg: ArchConfig, params: Params, loads) -> Params:
    """``params`` with the sigmoid-routed layers' bias buffers moved one
    step by ``loads`` (MoE layers, E), the step's expert loads."""
    layers = dict(params["layers"])
    key = "moe" if cfg.first_k_dense else None
    stack = dict(layers[key]) if key else layers
    ffn = dict(stack["ffn"])
    ffn["router_bias"] = moe_lib.bias_update(cfg, ffn["router_bias"], loads)
    stack["ffn"] = ffn
    if key:
        layers[key] = stack
    return dict(params, layers=layers if key else stack)


def embed(cfg: ArchConfig, params: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    """The rows of ``tokens``, gathered in float32 and then cast: the
    gradient of a token repeated in the batch is summed in float32."""
    return params["tok_embed"]["table"][tokens].astype(cfg.dtype)


def logits_fn(cfg: ArchConfig, params: Params, x: jnp.ndarray) -> jnp.ndarray:
    x = cm.rmsnorm(x, params["final_norm"])
    table = params["lm_head"]["table"].astype(cfg.dtype)
    return x @ table.T


def train_loss(cfg: ArchConfig, params: Params, batch: Dict[str, jnp.ndarray],
               *, remat: bool = True, sampled_softmax: bool = False) -> jnp.ndarray:
    return train_loss_stats(cfg, params, batch, remat=remat,
                            sampled_softmax=sampled_softmax)[0]


def train_loss_stats(cfg: ArchConfig, params: Params,
                     batch: Dict[str, jnp.ndarray], *, remat: bool = True,
                     sampled_softmax: bool = False,
                     x: Optional[jnp.ndarray] = None):
    """(loss, routing stats).  ``x``: the embedded tokens (b, s, d), where
    the caller gathers them itself (``params`` then need no
    ``tok_embed``).  ``batch['loss_mask']`` (b, s), where given, weights
    each position's loss."""
    tokens, labels = batch["tokens"], batch["labels"]
    b, s = tokens.shape
    if x is None:
        x = embed(cfg, params, tokens)
    x = cm.shard_act(x, "model", None)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x, aux, stats = backbone_train_stats(cfg, params, x, positions,
                                         remat=remat)
    x = cm.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if sampled_softmax:
        loss = cm.sampled_softmax_xent(
            x.reshape(b * s, -1), params["lm_head"]["table"],
            labels.reshape(-1), batch["neg_ids"])
    else:
        loss = cm.chunked_softmax_xent(
            x, params["lm_head"]["table"], labels, cfg.loss_chunk,
            mask=batch.get("loss_mask"))
    return loss + cfg.aux_loss_alpha * aux, stats


def init_cache(cfg: ArchConfig, batch: int, max_seq: int, dtype=None):
    _no_serving(cfg)
    dtype = dtype or cfg.dtype
    sub = (2,) if uses_blocks(cfg) else ()
    shape = (n_scan_units(cfg),) + sub + (batch, max_seq, cfg.n_kv,
                                          cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "len": jnp.zeros((), jnp.int32)}


def prefill(cfg: ArchConfig, params: Params, tokens: jnp.ndarray,
            max_seq: Optional[int] = None):
    """Returns (last-position logits (b, vocab), cache)."""
    _no_serving(cfg)
    b, s = tokens.shape
    max_seq = max_seq or s
    x = embed(cfg, params, tokens)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    blocks = uses_blocks(cfg)
    dcfg = _dense_cfg(cfg) if blocks else None

    def body(h, lp):
        if blocks:
            h, (k1, v1) = layer_prefill(dcfg, lp["dense"], h, positions)
            h, (k2, v2) = layer_prefill(cfg, lp["moe"], h, positions)
            k = jnp.stack([k1, k2])
            v = jnp.stack([v1, v2])
        else:
            h, (k, v) = layer_prefill(cfg, lp, h, positions)
        return h, (k.astype(cfg.dtype), v.astype(cfg.dtype))

    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    if max_seq > s:
        pad = [(0, 0)] * (ks.ndim - 3) + [(0, max_seq - s), (0, 0), (0, 0)]
        ks = jnp.pad(ks, pad)
        vs = jnp.pad(vs, pad)
    logits = logits_fn(cfg, params, x[:, -1:])[:, 0]
    cache = {"k": ks, "v": vs, "len": jnp.asarray(s, jnp.int32)}
    return logits, cache


def decode_step(cfg: ArchConfig, params: Params, cache, token: jnp.ndarray):
    """token (b,) int32.  Returns (logits (b, vocab), cache')."""
    b = token.shape[0]
    x = embed(cfg, params, token[:, None])
    pos = cache["len"]
    blocks = uses_blocks(cfg)
    dcfg = _dense_cfg(cfg) if blocks else None

    def body(h, xs):
        lp, ck, cv = xs
        if blocks:
            h, ck1, cv1 = layer_decode(dcfg, lp["dense"], h, ck[0], cv[0], pos)
            h, ck2, cv2 = layer_decode(cfg, lp["moe"], h, ck[1], cv[1], pos)
            ck = jnp.stack([ck1, ck2])
            cv = jnp.stack([cv1, cv2])
        else:
            h, ck, cv = layer_decode(cfg, lp, h, ck, cv, pos)
        return h, (ck, cv)

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    logits = logits_fn(cfg, params, x)[:, 0]
    return logits, {"k": ks, "v": vs, "len": pos + 1}
