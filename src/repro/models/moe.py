"""Mixture-of-Experts FFN — sort-based (MegaBlocks-style) dispatch.

TPU-native choice (DESIGN.md §3/§4): instead of the GShard one-hot dispatch
einsum (whose (T, E, C) mask is ~10 GB at our 4k-train cell), tokens are
*sorted by expert id* and gathered into an (E, C, d) buffer — O(T·K) sort +
two gathers.  Capacity overflow drops tokens (standard).  Sharding:

  * ``expert_sharding='ep'``  — experts over the 'model' axis (llama4:
    128/16 = 8 per shard); GSPMD turns the gather/scatter into all-to-alls.
  * ``expert_sharding='tp'``  — expert count not divisible (qwen2-moe's
    60): shard each expert's d_ff over 'model' instead.

Shared experts (qwen2-moe: 4 merged into one wide SwiGLU; llama4: 1) are a
plain dense FFN added to the routed output.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.models.config import ArchConfig


def moe_init(key, cfg: ArchConfig):
    ks = jax.random.split(key, 7)
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    p = {
        "router": cm.dense_init(ks[0], d, E, scale=0.02),
        "w_gate": (jax.random.normal(ks[1], (E, d, f), jnp.float32)
                   / jnp.sqrt(d)).astype(jnp.float32),
        "w_up": (jax.random.normal(ks[2], (E, d, f), jnp.float32)
                 / jnp.sqrt(d)).astype(jnp.float32),
        "w_down": (jax.random.normal(ks[3], (E, f, d), jnp.float32)
                   / jnp.sqrt(f)).astype(jnp.float32),
    }
    if cfg.shared_d_ff:
        p["shared"] = {
            "w_gate": cm.dense_init(ks[4], d, cfg.shared_d_ff),
            "w_up": cm.dense_init(ks[5], d, cfg.shared_d_ff),
            "w_down": cm.dense_init(ks[6], cfg.shared_d_ff, d),
        }
    return p


def _capacity(cfg: ArchConfig, n_assign: int) -> int:
    c = int(n_assign * cfg.capacity_factor / cfg.n_experts)
    return max(8, -(-c // 8) * 8)


def _n_groups(cfg: ArchConfig, T: int) -> int:
    g = min(cfg.moe_groups, T)
    while T % g != 0:
        g -= 1
    return max(g, 1)


def _dispatch_group(cfg: ArchConfig, x, eids, gates, C: int):
    """Sort-based dispatch for ONE group.  x (Tg, d); eids/gates (Tg, K).
    Returns (xe (E, C, d), ts, slot, keep, gs) for the combine."""
    Tg, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    dt = x.dtype
    n_assign = Tg * K
    e_flat = eids.reshape(-1)
    g_flat = gates.reshape(-1)
    t_flat = jnp.repeat(jnp.arange(Tg, dtype=jnp.int32), K)
    perm = jnp.argsort(e_flat)
    es, ts, gs = e_flat[perm], t_flat[perm], g_flat[perm]
    counts = jax.ops.segment_sum(jnp.ones_like(es), es, num_segments=E)
    offsets = jnp.cumsum(counts) - counts
    rank = jnp.arange(n_assign, dtype=jnp.int32) - offsets[es].astype(jnp.int32)
    keep = rank < C
    slot = jnp.where(keep, es * C + rank, E * C)     # overflow -> dump row
    xbuf = jnp.zeros((E * C + 1, d), dt).at[slot].set(x[ts])
    return xbuf[: E * C].reshape(E, C, d), ts, slot, keep, gs


def _combine_group(cfg: ArchConfig, ye, ts, slot, keep, gs, Tg: int):
    E = cfg.n_experts
    C = ye.shape[1]
    d = ye.shape[-1]
    dt = ye.dtype
    y_rows = ye.reshape(E * C, d)
    contrib = jnp.where(keep[:, None], y_rows[jnp.minimum(slot, E * C - 1)], 0.0)
    contrib = contrib * gs[:, None].astype(dt)
    return jnp.zeros((Tg, d), dt).at[ts].add(contrib)


def moe_apply(cfg: ArchConfig, p, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (T, d) tokens.  Returns (y (T, d), aux_loss ()).

    GROUPED dispatch (GShard-style): tokens are split into
    ``cfg.moe_groups`` groups aligned with the DP shards, and the
    sort/gather/scatter run *per group* (vmapped, leading axis sharded
    over ('pod','data')).  With a single global group the dispatch
    gathers index into the full (T, d) token buffer — GSPMD cannot prove
    locality and all-gathers ~10 GB/device at the 4k-train cells
    (measured; EXPERIMENTS.md §Perf).  Per-group capacity also matches
    how real MoE frameworks enforce it.  Expert weights stay sharded
    over 'model' (EP or per-expert TP); GSPMD inserts the all-to-all at
    the (G, E, C, d) buffer boundary."""
    T, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    dt = x.dtype

    logits = (x @ p["router"].astype(dt)).astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eids = jax.lax.top_k(probs, K)                        # (T, K)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

    # Switch-style load-balance auxiliary loss (global).
    me = jnp.mean(probs, axis=0)                                 # (E,)
    ce = jnp.mean(jax.nn.one_hot(eids[:, 0], E, dtype=jnp.float32), axis=0)
    aux = jnp.sum(me * ce) * E

    G = _n_groups(cfg, T)
    Tg = T // G
    C = _capacity(cfg, Tg * K)
    xg = _shard(x.reshape(G, Tg, d), (("pod", "data"), None, None))
    eg = eids.reshape(G, Tg, K)
    gg = gates.reshape(G, Tg, K)

    xe, ts, slot, keep, gs = jax.vmap(
        lambda xi, ei, gi: _dispatch_group(cfg, xi, ei, gi, C))(xg, eg, gg)
    if cfg.expert_sharding == "ep":
        xe = _shard(xe, (("pod", "data"), "model", None, None))
    else:
        xe = _shard(xe, (("pod", "data"), None, None, "model"))

    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    # FSDP: pin the bf16 cast BEFORE the weight all-gather — otherwise
    # GSPMD gathers the f32 master shards and converts after (2x the
    # gather traffic and 2x the gathered-weight temps; §Perf llama4 L3)
    if cfg.expert_sharding == "ep":
        wspec = ("model", None, None)
        wdspec = wspec
    else:
        wspec = (None, None, "model")
        wdspec = (None, "model", None)
    # pin the bf16 cast's sharding so the FSDP all-gather moves bf16
    # weights, not the f32 master (EXPERIMENTS.md §Perf llama4 L3; the
    # stronger barrier variants L4/L4b were refuted and removed).  Only
    # worthwhile when enough tokens route to amortize the gather — decode
    # (T ≈ batch) skips it, keeping weights FSDP-sharded.
    pin = T >= 8 * E
    wg = _shard(p["w_gate"].astype(dt), wspec) if pin \
        else p["w_gate"].astype(dt)
    wu = _shard(p["w_up"].astype(dt), wspec) if pin \
        else p["w_up"].astype(dt)
    wd = _shard(p["w_down"].astype(dt), wdspec) if pin \
        else p["w_down"].astype(dt)
    h = act(jnp.einsum("gecd,edf->gecf", xe, wg)) * \
        jnp.einsum("gecd,edf->gecf", xe, wu)
    if cfg.expert_sharding == "tp":
        h = _shard(h, (("pod", "data"), None, None, "model"))
    ye = jnp.einsum("gecf,efd->gecd", h, wd)

    y = jax.vmap(
        lambda yi, t, s, k, g: _combine_group(cfg, yi, t, s, k, g, Tg))(
            ye, ts, slot, keep, gs)
    y = _shard(y, (("pod", "data"), None, None)).reshape(T, d)

    if cfg.shared_d_ff:
        sp = p["shared"]
        hs = act(x @ sp["w_gate"].astype(dt)) * (x @ sp["w_up"].astype(dt))
        hs = _shard(hs.reshape(G, Tg, -1), (("pod", "data"), None, "model"))
        y = y + (hs @ sp["w_down"].astype(dt)).reshape(T, d)
    return y, aux


def _shard(x, axes):
    """Sharding constraint — ``sharding.constraint`` is a no-op outside a
    mesh context and drops axes the mesh lacks or cannot divide."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import constraint
    return constraint(x, P(*axes))
