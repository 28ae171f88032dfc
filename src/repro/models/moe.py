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

``cfg.router == 'sigmoid'`` is DeepSeek-V3's router (arXiv:2412.19437
§2.1.2) over the experts this chip holds (``moe_apply_held``): it scores
all ``n_experts``, computes only its own experts' part of the result and
drops nothing.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.models.config import ArchConfig
from repro.obs.profiling import scope


def moe_init(key, cfg: ArchConfig):
    ks = jax.random.split(key, 7)
    E, d, f = cfg.experts_held, cfg.d_model, cfg.d_ff
    p = {
        "router": cm.dense_init(ks[0], d, cfg.n_experts, scale=0.02),
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
    if cfg.router == "sigmoid":
        # a buffer, not a weight: the step moves it by the loads it sees
        p["router_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
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


# ---------------------------------------------------------------------------
# DeepSeek-V3 routing over the experts held here (expert parallelism)
# ---------------------------------------------------------------------------

def route_sigmoid(cfg: ArchConfig, p, x: jnp.ndarray):
    """x (T, d) -> (scores (T, E) f32, expert ids (T, K), gates (T, K)).
    Scores are sigmoid(x W_r) in float32, as the published gate computes
    them; the top-K is chosen on score + bias, the gates are the chosen
    raw scores, normalised to sum one and scaled by ``routed_scaling``."""
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    scores = jax.nn.sigmoid(logits)
    bias = jax.lax.stop_gradient(p["router_bias"])
    _, eids = jax.lax.top_k(scores + bias, cfg.top_k)
    w = jnp.take_along_axis(scores, eids, axis=-1)
    gates = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return scores, eids, gates * cfg.routed_scaling


def balance_loss(cfg: ArchConfig, scores: jnp.ndarray,
                 eids: jnp.ndarray) -> jnp.ndarray:
    """The sequence-wise balance loss Σ_i f_i·P_i, averaged over the batch
    (unweighted; the caller multiplies by α).  scores (b, s, E), eids
    (b, s, K): f_i = E/(K·s)·#{t: i chosen at t}, P_i = mean_t s_i,t / Σ_j
    s_j,t, both per sequence."""
    b, s, E = scores.shape
    chosen = jax.vmap(lambda e: jnp.zeros((E,), jnp.float32).at[
        e.reshape(-1)].add(1.0))(eids)                        # (b, E)
    f = chosen * (E / (cfg.top_k * s))
    P = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=1)
    return jnp.mean(jnp.sum(f * P, axis=-1))


def expert_loads(cfg: ArchConfig, eids: jnp.ndarray) -> jnp.ndarray:
    """(E,) int32: the (token, slot) pairs each expert received."""
    return jnp.zeros((cfg.n_experts,), jnp.int32).at[
        eids.reshape(-1)].add(1)


def bias_update(cfg: ArchConfig, bias: jnp.ndarray,
                loads: jnp.ndarray) -> jnp.ndarray:
    """b_i += γ·sign(mean load − load_i): underloaded experts gain.
    ``loads`` (..., E), the mean over the last axis."""
    load = loads.astype(jnp.float32)
    mean = jnp.mean(load, axis=-1, keepdims=True)
    return bias + cfg.bias_update_rate * jnp.sign(mean - load)


def grouped_matmul_backend() -> str:
    """``gmm``: the megablox Pallas kernel (a TPU), whose grid runs only the
    row tiles of non-empty groups; ``ragged_dot`` elsewhere.  (Tests name
    ``interpret``: the kernel under the Pallas interpreter.)"""
    return "gmm" if jax.default_backend() == "tpu" else "ragged_dot"


def _gmm_tiling(m: int, k: int, n: int):
    """(tm, tk, tn) for a megablox call: 512-row tiles, and each of k, n
    whole or in 512-wide tiles (1,408 = 11·128 stays whole)."""
    tm = next(t for t in (512, 256, 128, 64, 32, 16, 8) if m % t == 0)
    return (tm, 512 if k % 512 == 0 else k, 512 if n % 512 == 0 else n)


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   group_sizes: jnp.ndarray) -> jnp.ndarray:
    """lhs (m, k) rows in groups of ``group_sizes`` (G,), rhs (G, k, n):
    row r of group g times rhs[g]; rows past Σ group_sizes are padding,
    and their output is undefined (the caller masks it)."""
    backend = grouped_matmul_backend()
    if backend in ("gmm", "interpret"):
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        return gmm(lhs, rhs, group_sizes, lhs.dtype, _gmm_tiling, None,
                   None, False, backend == "interpret")
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=lhs.dtype)


def dispatch_held(cfg: ArchConfig, eids: jnp.ndarray):
    """Sort the (token, slot) pairs so those routed to the held experts
    come first, grouped by expert.  Returns (order (T·K,), the token of
    each sorted pair, group sizes (held,) int32, how many are here)."""
    T, K = eids.shape
    held = cfg.experts_held
    local = eids.reshape(-1) - cfg.expert_rank * held
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    tokens = (order // K).astype(jnp.int32)
    return order, tokens, sizes, jnp.sum(sizes)


def _held_chunk(cfg: ArchConfig, p, xt, eids, gates):
    """The held experts' part of the result for one chunk of tokens: xt
    (Tc, d), eids and gates (Tc, K).  Returns (y (Tc, d), pairs here)."""
    Tc, d = xt.shape
    dt = xt.dtype
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    with scope("obs.router"):
        order, tok, sizes, n_here = dispatch_held(cfg, eids)
        valid = (jnp.arange(Tc * cfg.top_k) < n_here)[:, None]
        xs = jnp.where(valid, xt[tok], 0).astype(dt)
    with scope("obs.experts"):
        h = act(grouped_matmul(xs, p["w_gate"].astype(dt), sizes)) * \
            grouped_matmul(xs, p["w_up"].astype(dt), sizes)
        ye = grouped_matmul(h, p["w_down"].astype(dt), sizes)
    with scope("obs.router"):
        # padding rows of the kernels' outputs are never written: mask
        # them before anything multiplies them, in the backward too
        ye = jnp.where(valid, ye, 0).astype(jnp.float32)
        ye = (ye * gates.reshape(-1)[order][:, None]).astype(dt)
        y = jnp.zeros((Tc, d), dt).at[tok].add(ye)
    return y, n_here


# (token, slot) rows one dispatch chunk holds: 4,096 tokens of 6 slots
# keep the chunk's buffers near 100 MB at hidden size 2,048
CHUNK_ROWS = 32768


def token_chunks(cfg: ArchConfig, T: int) -> int:
    """How many chunks the routed part runs in: the fewest that keep each
    chunk's (token, slot) buffer within ``CHUNK_ROWS`` rows."""
    n = 1
    while T % (2 * n) == 0 and (T // n) * cfg.top_k > CHUNK_ROWS:
        n *= 2
    return n


def moe_apply_held(cfg: ArchConfig, p, x: jnp.ndarray):
    """x (b, s, d) -> (y (b, s, d), balance loss, stats).

    Routes every token over all ``n_experts`` and computes the part of the
    result that the held experts give, for every (token, slot) pair routed
    to one of them (none dropped), plus the shared experts.  The routed
    part runs over chunks of tokens (``token_chunks``), each rematerialised
    in the backward, so that one chunk's dispatch buffer is live at a time.
    ``stats``: ``loads`` (E,) for the bias update and ``routed_here``, the
    pairs the held experts computed.  Scopes: ``obs.router`` (scores,
    top-k, sort, dispatch, combine) and ``obs.experts`` (the held experts'
    grouped matmuls)."""
    b, s, d = x.shape
    T = b * s
    xt = x.reshape(T, d)
    dt = x.dtype
    with scope("obs.router"):
        scores, eids, gates = route_sigmoid(cfg, p, xt)
        aux = balance_loss(cfg, scores.reshape(b, s, -1),
                           eids.reshape(b, s, -1))
    n = token_chunks(cfg, T)
    if n == 1:
        y, n_here = _held_chunk(cfg, p, xt, eids, gates)
    else:
        body = jax.checkpoint(
            lambda xs: _held_chunk(cfg, p, *xs), prevent_cse=False)
        y, per = jax.lax.map(body, (xt.reshape(n, T // n, d),
                                    eids.reshape(n, T // n, -1),
                                    gates.reshape(n, T // n, -1)))
        y, n_here = y.reshape(T, d), jnp.sum(per)
    if cfg.shared_d_ff:
        act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
        sp = p["shared"]
        hs = act(xt @ sp["w_gate"].astype(dt)) * (xt @ sp["w_up"].astype(dt))
        y = y + hs @ sp["w_down"].astype(dt)
    stats = {"loads": expert_loads(cfg, eids), "routed_here": n_here}
    return y.reshape(b, s, d), aux, stats
