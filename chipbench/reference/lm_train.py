"""Plain reference of the Moonlight-16B-A3B cells: three training steps of
one chip's expert-parallel share, in float32 at the highest matmul
precision, written from the published equations.

The model, per layer ``x ← x + MLA(RMSNorm(x)); x ← x + FFN(RMSNorm(x))``:

* MLA (DeepSeek-V2 §2.1, ``q_lora_rank`` null), head by head: q = x W_q
  split into nope and rope parts; [c, k_rope] = x W_kv_a; c = RMSNorm(c);
  [k_nope, v] = c W_kv_b; RoPE (rotate-half) on q_rope and on the one
  k_rope all heads share; softmax(q·k/√(nope+rope), causal) v; then W_o.
* FFN: SwiGLU of width ``intermediate_size`` in the leading dense layers;
  in the others (DeepSeek-V3 §2.1.2) sigmoid scores s = σ(x W_r) over all
  ``n_routed_experts``, the top ``num_experts_per_tok`` of s + b (b a
  bias buffer), gates = chosen s / Σ chosen s × ``routed_scaling_factor``;
  the experts held here (a loop over them, each over every token with its
  gate, zero where not chosen: no dispatch buffer, nothing dropped) plus
  the shared experts (one SwiGLU ``n_shared_experts`` × wide).
* Loss: full-softmax cross-entropy of the next token (the last position
  masked), computed in blocks of the sequence, plus α × the sequence-wise
  balance loss Σ_i f_i P_i of each MoE layer (f_i = E/(K·s)·#chosen,
  P_i = mean of s_i / Σ_j s_j, per sequence, mean over sequences).

The update, at η(t) (``lr_schedule``): count-sketch Adam on ``tok_embed``
one unique id after another in ascending order (the sparse-rows kernel's order, with its
direction clamp; ``sparse_train._stream``), on ``lm_head`` over every row
at once from the pre-step sketches; plain Adam on every other leaf; then
b_i += γ·sign(mean load − load_i).  Weights are drawn by the benchmark
(``chipbench.weights``) from the seed, leaf by leaf, as the runner draws
them.  ``control`` computes every array and operation in that dtype."""
from __future__ import annotations

import functools
import zlib
from typing import Dict, List

import numpy as np

from chipbench import weights
from chipbench.reference import countsketch as cs
from chipbench.reference.sparse_train import PAD, _stream

LOSS_BLOCK = 512          # sequence positions per block of the loss
UPDATE_ROWS = 8192        # head rows per block of its sketch update


def sizes(cfg) -> dict:
    """The sizes the reference reads from the configuration file."""
    ff = int(cfg["moe_intermediate_size"])
    return {"d": int(cfg["hidden_size"]), "h": int(cfg["num_attention_heads"]),
            "r": int(cfg["kv_lora_rank"]), "dn": int(cfg["qk_nope_head_dim"]),
            "dr": int(cfg["qk_rope_head_dim"]), "dv": int(cfg["v_head_dim"]),
            "ff_dense": int(cfg["intermediate_size"]), "ff": ff,
            "shared": int(cfg["n_shared_experts"]) * ff,
            "E": int(cfg["n_routed_experts"]),
            "K": int(cfg["num_experts_per_tok"]),
            "held": int(cfg["n_experts_held"]),
            "rank": int(cfg["expert_rank"]),
            "n_dense": int(cfg["first_k_dense_replace"]),
            "n_moe": int(cfg["n_layers"]) - int(cfg["first_k_dense_replace"]),
            "V": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "scaling": float(cfg["routed_scaling_factor"]),
            "gamma": float(cfg["router"]["bias_update_rate"]),
            "alpha": float(cfg["router"]["balance_loss_alpha"])}


def param_shapes(cfg) -> Dict[str, tuple]:
    """Every parameter leaf, by the path the program's tree gives it: the
    layers stacked per group (``dense``, then ``moe``)."""
    a = sizes(cfg)
    d, h, r = a["d"], a["h"], a["r"]
    out = {"tok_embed/table": (a["V"], d), "lm_head/table": (a["V"], d),
           "final_norm": (d,)}
    for group, n in (("dense", a["n_dense"]), ("moe", a["n_moe"])):
        p = f"layers/{group}/"
        out.update({p + "ln1": (n, d), p + "ln2": (n, d),
                    p + "attn/wq": (n, d, h * (a["dn"] + a["dr"])),
                    p + "attn/w_kv_a": (n, d, r + a["dr"]),
                    p + "attn/kv_norm": (n, r),
                    p + "attn/w_kv_b": (n, r, h * (a["dn"] + a["dv"])),
                    p + "attn/wo": (n, h * a["dv"], d)})
        if group == "dense":
            f = a["ff_dense"]
            out.update({p + "ffn/w_gate": (n, d, f), p + "ffn/w_up": (n, d, f),
                        p + "ffn/w_down": (n, f, d)})
        else:
            e, f, s = a["held"], a["ff"], a["shared"]
            out.update({p + "ffn/router": (n, d, a["E"]),
                        p + "ffn/router_bias": (n, a["E"]),
                        p + "ffn/w_gate": (n, e, d, f),
                        p + "ffn/w_up": (n, e, d, f),
                        p + "ffn/w_down": (n, e, f, d),
                        p + "ffn/shared/w_gate": (n, d, s),
                        p + "ffn/shared/w_up": (n, d, s),
                        p + "ffn/shared/w_down": (n, s, d)})
    return out


def init_rule(path: str, shape):
    """A leaf's initial value: 'ones' (norm scales), 'zeros' (the router
    bias), else a normal draw times this scale (the tables and the router
    0.02, a weight 1/√fan-in)."""
    name = path.rsplit("/", 1)[-1]
    if name in ("ln1", "ln2", "kv_norm", "final_norm"):
        return "ones"
    if name == "router_bias":
        return "zeros"
    if name in ("table", "router"):
        return 0.02
    return float(shape[-2]) ** -0.5


def lr_schedule(opt: dict):
    """η(t) of step t (1, 2, ...): a linear warm-up to ``lr`` over
    ``warmup_steps``, in float32."""
    import jax.numpy as jnp
    peak, warm = float(opt["lr"]), float(opt["warmup_steps"])
    return lambda t: peak * jnp.minimum(1.0, jnp.asarray(t, jnp.float32)
                                        / warm)


def stream(path: str) -> int:
    """The leaf's stream of the seed (``weights.seed_key``)."""
    return 100 + (zlib.crc32(path.encode()) & 0x7FFFFFF)


def draw(seed: int, path: str, shape, dtype=None):
    import jax.numpy as jnp
    rule = init_rule(path, shape)
    if rule == "ones":
        x = jnp.ones(shape, jnp.float32)
    elif rule == "zeros":
        x = jnp.zeros(shape, jnp.float32)
    else:
        x = weights.draw(weights.seed_key(seed, stream(path)),
                         shape=tuple(shape), scale=rule)
    return x if dtype is None else x.astype(dtype)


def change_norm(seed: int, path: str, x) -> float:
    """‖x − x₀‖, with x₀ the leaf's initial value (drawn again in blocks)."""
    import jax.numpy as jnp
    rule = init_rule(path, x.shape)
    if rule in ("ones", "zeros"):
        x0 = 1.0 if rule == "ones" else 0.0
        return float(jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - x0))))
    return float(weights.change_sq(
        x, weights.seed_key(seed, stream(path)), scale=rule)) ** 0.5


# -- the model --------------------------------------------------------------

def _rmsnorm(x, w, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _rope(x, theta):
    """Rotate-half RoPE over the last axis of x (b, s, hd), position = s."""
    import jax.numpy as jnp
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mla(a, P, x):
    """Latent attention head by head (a scan over the heads, each
    rematerialised in the backward)."""
    import jax
    import jax.numpy as jnp
    b, s, d = x.shape
    h, r, dn, dr, dv = a["h"], a["r"], a["dn"], a["dr"], a["dv"]
    kv_a = x @ P["attn/w_kv_a"]
    c = _rmsnorm(kv_a[..., :r], P["attn/kv_norm"], a["eps"])
    k_rope = _rope(kv_a[..., r:], a["theta"])
    causal = jnp.tril(jnp.ones((s, s), bool))
    scale = 1.0 / np.sqrt(dn + dr)

    @jax.checkpoint
    def head(acc, w):
        wq, wkv, wo = w
        q = x @ wq
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], a["theta"])], -1)
        kv = c @ wkv
        k = jnp.concatenate([kv[..., :dn], k_rope], -1)
        sc = jnp.einsum("bqc,bkc->bqk", q, k) * jnp.asarray(scale, x.dtype)
        sc = jnp.where(causal, sc, jnp.asarray(-1e30, sc.dtype))
        p = jax.nn.softmax(sc, axis=-1)
        return acc + (p @ kv[..., dn:]) @ wo, None

    per_head = (P["attn/wq"].reshape(d, h, dn + dr).transpose(1, 0, 2),
                P["attn/w_kv_b"].reshape(r, h, dn + dv).transpose(1, 0, 2),
                P["attn/wo"].reshape(h, dv, d))
    out, _ = jax.lax.scan(head, jnp.zeros_like(x), per_head)
    return out


def _swiglu(x, wg, wu, wd):
    import jax
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def _moe(a, P, x):
    """The held experts' part of the routed result plus the shared
    experts; returns it with the balance loss and the expert loads."""
    import jax
    import jax.numpy as jnp
    b, s, d = x.shape
    E, K = a["E"], a["K"]
    xt = x.reshape(b * s, d)
    scores = jax.nn.sigmoid(xt @ P["ffn/router"])
    _, ids = jax.lax.top_k(scores + P["ffn/router_bias"], K)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    gates = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * a["scaling"]
    loads = jnp.zeros((E,), jnp.int32).at[ids.reshape(-1)].add(1)
    # sequence-wise balance loss
    chosen = jax.nn.one_hot(ids.reshape(b, s * K), E, dtype=x.dtype).sum(1)
    f = chosen * (E / (K * s))
    sn = scores.reshape(b, s, E)
    P_i = jnp.mean(sn / jnp.sum(sn, axis=-1, keepdims=True), axis=1)
    balance = jnp.mean(jnp.sum(f * P_i, axis=-1))

    @jax.checkpoint
    def expert(y, xs):
        e, wg, wu, wd = xs
        g = jnp.sum(jnp.where(ids == e, gates, 0), axis=-1)
        return y + g[:, None] * _swiglu(xt, wg, wu, wd), None

    first = a["rank"] * a["held"]
    y = _swiglu(xt, P["ffn/shared/w_gate"], P["ffn/shared/w_up"],
                P["ffn/shared/w_down"])
    y, _ = jax.lax.scan(expert, y, (first + jnp.arange(a["held"]),
                                    P["ffn/w_gate"], P["ffn/w_up"],
                                    P["ffn/w_down"]))
    return y.reshape(b, s, d), balance, loads


def _xent(a, x, W, labels, mask):
    """Σ of the masked next-token losses, in blocks of the sequence."""
    import jax
    import jax.numpy as jnp
    b, s, d = x.shape
    nb = max(1, s // LOSS_BLOCK)
    blk = s // nb

    @jax.checkpoint
    def body(tot, xs):
        xb, lb, mb = xs
        logits = xb @ W.T
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lb[..., None], -1)[..., 0]
        return tot + jnp.sum((logz - gold) * mb), None

    split = lambda t: jnp.moveaxis(  # noqa: E731
        t.reshape((b, nb, blk) + t.shape[2:]), 1, 0)
    tot, _ = jax.lax.scan(body, jnp.zeros((), x.dtype),
                          (split(x), split(labels), split(mask)))
    return tot


def _loss(a, P, rows, tokens):
    """(loss, loads (MoE layers, E)) of one batch; ``rows`` the looked-up
    embedding rows (b, s, d)."""
    import jax
    import jax.numpy as jnp
    b, s = tokens.shape
    x = rows
    balance = jnp.zeros((), rows.dtype)
    loads = []
    for group, n in (("dense", a["n_dense"]), ("moe", a["n_moe"])):
        for i in range(n):
            L = {k.split("/", 2)[2]: v[i] for k, v in P.items()
                 if k.startswith(f"layers/{group}/")}

            @jax.checkpoint
            def layer(x, L=L, group=group):
                x = x + _mla(a, L, _rmsnorm(x, L["ln1"], a["eps"]))
                hN = _rmsnorm(x, L["ln2"], a["eps"])
                if group == "dense":
                    return x + _swiglu(hN, L["ffn/w_gate"], L["ffn/w_up"],
                                       L["ffn/w_down"]), None, None
                y, bal, ld = _moe(a, L, hN)
                return x + y, bal, ld

            x, bal, ld = layer(x)
            if bal is not None:
                balance = balance + bal
                loads.append(ld)
    x = _rmsnorm(x, P["final_norm"], a["eps"])
    labels = jnp.roll(tokens, -1, axis=1)
    mask = jnp.broadcast_to(jnp.arange(s) < s - 1, (b, s)).astype(x.dtype)
    nll = _xent(a, x, P["lm_head/table"], labels, mask) / jnp.sum(mask)
    return nll + a["alpha"] * balance, jnp.stack(loads)


@functools.lru_cache(maxsize=8)
def _grad_fn(key):
    import jax
    a = dict(key)

    def f(P, rows, tokens):
        (loss, loads), (gP, grows) = jax.value_and_grad(
            lambda P, r: _loss(a, P, r, tokens), argnums=(0, 1),
            has_aux=True)(P, rows)
        return loss, loads, gP, grows

    return jax.jit(f)


@functools.lru_cache(maxsize=8)
def _adam_leaf(b1, b2, eps):
    import jax
    import jax.numpy as jnp

    def f(p, m, v, g, t, lr):
        dt = p.dtype
        m = jnp.asarray(b1, dt) * m + jnp.asarray(1 - b1, dt) * g
        v = jnp.asarray(b2, dt) * v + jnp.asarray(1 - b2, dt) * g * g
        bc1 = (1 - jnp.asarray(b1, jnp.float32) ** t).astype(dt)
        bc2 = (1 - jnp.asarray(b2, jnp.float32) ** t).astype(dt)
        d = (m / bc1) / (jnp.sqrt(v / bc2) + jnp.asarray(eps, dt))
        return p - lr.astype(dt) * d, m, v

    return jax.jit(f)


@functools.lru_cache(maxsize=8)
def _cs_adam_all_rows(b1, b2, eps, seed, depth, width):
    """Count-sketch Adam over every row of a (n, d) leaf at once: each
    row's estimates read from the pre-step sketches, the deltas of all
    rows added, the direction from estimate + delta (blocks of rows)."""
    import jax
    import jax.numpy as jnp

    def f(M, V, W, g, t, lr):
        dt = M.dtype
        n = W.shape[0]
        blk = UPDATE_ROWS if n % UPDATE_ROWS == 0 else n
        bc1 = (1 - jnp.asarray(b1, jnp.float32) ** t).astype(dt)
        bc2 = (1 - jnp.asarray(b2, jnp.float32) ** t).astype(dt)
        rows_j = jnp.arange(depth)[:, None]

        def body(carry, i):
            Mn, Vn, W = carry
            ids = i * blk + jnp.arange(blk)
            bk = cs.buckets(seed, depth, width, ids)
            sg = cs.signs(seed, depth, ids).astype(dt)[..., None]
            gb = jax.lax.dynamic_slice_in_dim(g, i * blk, blk)
            live = jnp.any(gb != 0, axis=-1, keepdims=True).astype(dt)
            m_old = jnp.median(M[rows_j, bk] * sg, axis=0)
            v_old = jnp.min(V[rows_j, bk], axis=0)
            dm = jnp.asarray(1 - b1, dt) * (gb - m_old) * live
            dv = jnp.asarray(1 - b2, dt) * (gb * gb - v_old) * live
            Mn = Mn.at[rows_j, bk].add(sg * dm[None])
            Vn = Vn.at[rows_j, bk].add(jnp.broadcast_to(dv[None],
                                                        (depth,) + dv.shape))
            d = ((m_old + dm) / bc1) / (
                jnp.sqrt(jnp.maximum(v_old + dv, 0) / bc2)
                + jnp.asarray(eps, dt))
            wb = jax.lax.dynamic_slice_in_dim(W, i * blk, blk)
            W = jax.lax.dynamic_update_slice_in_dim(
                W, wb - lr.astype(dt) * live * d, i * blk, 0)
            return (Mn, Vn, W), None

        (M2, V2, W), _ = jax.lax.scan(body, (M, V, W), jnp.arange(n // blk))
        return M2, V2, W

    return jax.jit(f, donate_argnums=(2,))


@functools.lru_cache(maxsize=8)
def _embedding_stream(b1, b2, eps, clip):
    """``sparse_train._stream`` (the embedding's per-id order) with the
    step's learning rate an argument."""
    import jax
    return jax.jit(lambda M, V, W, bm, sm, u, g, live, t, lr: _stream(
        M, V, W, bm, sm, u, g, live, t, lr=lr, b1=b1, b2=b2, eps=eps,
        clip=clip), donate_argnums=(0, 1, 2))


def _norm(x) -> float:
    import jax.numpy as jnp
    return float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))


def numbers(cell, seed: int, batches: List[Dict], control=None) -> dict:
    """The reference's readings over the first three ``batches``: each
    step's loss, the first gradient as the moments hold it after step 1
    (every leaf), each leaf's change after step 3.  ``control`` names a
    lower precision (``bfloat16``) for every array and operation."""
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(control or "float32")
    cfg = cell.config
    a = sizes(cfg)
    opt = cfg["optimizer"]
    b1, b2, eps = (float(opt[k]) for k in ("b1", "b2", "eps"))
    lr_at = lr_schedule(opt)
    shapes = param_shapes(cfg)
    P = {p: draw(seed, p, s, dt) for p, s in shapes.items()
         if p != "tok_embed/table"}
    table = draw(seed, "tok_embed/table", shapes["tok_embed/table"], dt)
    dense = sorted(p for p in P if p != "lm_head/table")
    np_dt = np.dtype(jnp.zeros((), dt).dtype)
    mom = {p: [np.zeros(shapes[p], np_dt), np.zeros(shapes[p], np_dt)]
           for p in dense}
    sk = {name: cfg["sketch"][name] for name in ("tok_embed/table",
                                                 "lm_head/table")}
    sketch = {name: [jnp.zeros((s["depth"], s["width"], a["d"]), dt)
                     for _ in range(2)] for name, s in sk.items()}
    grad_fn = _grad_fn(tuple(sorted(a.items())))
    adam = _adam_leaf(b1, b2, eps)
    h = sk["lm_head/table"]
    head_step = _cs_adam_all_rows(b1, b2, eps, int(h["seed"]),
                                  int(h["depth"]), int(h["width"]))
    e = sk["tok_embed/table"]
    emb_stream = _embedding_stream(b1, b2, eps, float(opt["dir_clip"]))
    losses, grad, leaf_grad = [], {}, {}
    tokens = [np.asarray(bt["tokens"], np.int32) for bt in batches[:3]]
    uniq = [np.unique(t.reshape(-1), return_inverse=True) for t in tokens]
    k = -(-max(u.size for u, _ in uniq) // PAD) * PAD
    for t, (tok, (uids, inv)) in enumerate(zip(tokens, uniq), start=1):
        jt = jnp.asarray(t, jnp.float32)
        lr = lr_at(t)
        with jax.default_matmul_precision("highest"):
            jtok = jnp.asarray(tok)
            rows = table[jtok]
            loss, loads, gP, grows = grad_fn(P, rows, jtok)
            del rows
            # the embedding: the rows' gradients summed per unique id
            n_u = uids.size
            g_u = jax.ops.segment_sum(grows.reshape(-1, a["d"]),
                                      jnp.asarray(inv.reshape(-1)),
                                      num_segments=k)
            del grows
            pad = np.zeros(k, np.int64)
            pad[:n_u] = uids
            live = np.zeros((k, 1), np.float32)
            live[:n_u] = 1.0
            ju = jnp.asarray(pad, jnp.int32)
            if t == 1:
                leaf_grad = {p: _norm(g) for p, g in gP.items()}
                leaf_grad["tok_embed/table"] = _norm(g_u)
            M, V = sketch["tok_embed/table"]
            M, V, table = emb_stream(
                M, V, table, cs.buckets(int(e["seed"]), int(e["depth"]),
                                        int(e["width"]), ju),
                cs.signs(int(e["seed"]), int(e["depth"]), ju).astype(dt),
                ju, g_u.astype(dt), jnp.asarray(live, dt), jt,
                lr.astype(dt))
            sketch["tok_embed/table"] = [M, V]
            M, V = sketch["lm_head/table"]
            M, V, P["lm_head/table"] = head_step(M, V, P["lm_head/table"],
                                                 gP.pop("lm_head/table"), jt,
                                                 lr)
            sketch["lm_head/table"] = [M, V]
        losses.append(float(loss))
        for p in dense:
            m, v = (jnp.asarray(x) for x in mom[p])
            P[p], m, v = adam(P[p], m, v, gP.pop(p), jt, lr)
            mom[p] = [np.asarray(m), np.asarray(v)]
        bias = "layers/moe/ffn/router_bias"
        ld = loads.astype(jnp.float32)
        P[bias] = P[bias] + jnp.asarray(a["gamma"], dt) * jnp.sign(
            jnp.mean(ld, axis=-1, keepdims=True) - ld).astype(dt)
        if t == 1:
            grad = {"m": {p: float(np.sqrt(np.sum(np.square(
                        mom[p][0].astype(np.float32))))) / (1 - b1)
                          for p in dense},
                    "v": {p: float(np.sqrt(np.sum(np.square(
                        mom[p][1].astype(np.float32))))) / (1 - b2)
                          for p in dense}}
            for name, (M, V) in sketch.items():
                grad["m"][name] = _norm(M) / (1 - b1)
                grad["v"][name] = _norm(V) / (1 - b2)
    P["tok_embed/table"] = table
    change = {p: change_norm(seed, p, x) for p, x in P.items()}
    for x in list(P.values()) + [s for pair in sketch.values()
                                 for s in pair]:
        x.delete()
    return {"loss": losses, "grad": grad, "change": change,
            "leaf_grad": leaf_grad}
