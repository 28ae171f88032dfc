"""Moonlight-16B-A3B's pieces against plain versions, at small sizes:
attention with a value head width of its own, latent attention head by
head, the sigmoid router and its bias, the dropless dispatch over the
experts one chip holds, the expert-parallel share, the float32 sum of a
repeated token's embedding gradient, and the launcher's path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import attention as attn
from repro.models import common as cm
from repro.models import mla, moe, transformer

SMALL = configs.get("moonlight-16b-a3b-ep8").reduced(n_experts_held=2)


def _normal(key, shape, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(key), shape) * scale


# -- attention with dv != d_qk ----------------------------------------------

@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])
def test_flash_core_with_its_own_v_width(hq, hkv):
    """``flash_attention`` (the custom-VJP core) against
    ``chunked_attention``, forward and gradient, with v 16 wide against
    q/k 24."""
    q, k = _normal(0, (2, 32, hq, 24)), _normal(1, (2, 32, hkv, 24))
    v, w = _normal(2, (2, 32, hkv, 16)), _normal(3, (2, 32, hq, 16))

    def f(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    flash = f(lambda q, k, v: attn.flash_attention(q, k, v, True, 8))
    plain = f(lambda q, k, v: attn.chunked_attention(q, k, v, causal=True,
                                                     chunk=8))
    o = attn.flash_attention(q, k, v, True, 8)
    assert o.shape == (2, 32, hq, 16)
    np.testing.assert_allclose(
        o, attn.chunked_attention(q, k, v, causal=True, chunk=8),
        rtol=2e-5, atol=2e-6)
    for a, b in zip(jax.grad(flash, (0, 1, 2))(q, k, v),
                    jax.grad(plain, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


# -- latent attention ---------------------------------------------------------

def _rope(x, theta):
    """Rotate-half RoPE over (b, s, d), written out."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, d, 2) / d)
    ang = np.arange(x.shape[1])[:, None] * freqs
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x1 * np.sin(ang) + x2 * np.cos(ang)], -1)


def _mla_by_head(cfg, p, x):
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    x = np.asarray(x, np.float64)
    h, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    s = x.shape[1]
    kv_a = x @ p["w_kv_a"]
    c = kv_a[..., :r]
    c = c / np.sqrt(np.mean(c * c, -1, keepdims=True) + cfg.norm_eps) \
        * p["kv_norm"]
    k_rope = _rope(kv_a[..., r:], cfg.rope_theta)
    out = 0.0
    for i in range(h):
        q = x @ p["wq"][:, i * (dn + dr):(i + 1) * (dn + dr)]
        q = np.concatenate([q[..., :dn], _rope(q[..., dn:], cfg.rope_theta)],
                           -1)
        kv = c @ p["w_kv_b"][:, i * (dn + dv):(i + 1) * (dn + dv)]
        k = np.concatenate([kv[..., :dn], k_rope], -1)
        sc = np.einsum("bqc,bkc->bqk", q, k) / np.sqrt(dn + dr)
        sc = np.where(np.tril(np.ones((s, s), bool)), sc, -np.inf)
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        out = out + (pr @ kv[..., dn:]) @ p["wo"][i * dv:(i + 1) * dv]
    return out


def test_mla_matches_head_by_head():
    cfg = SMALL
    p = mla.mla_init(jax.random.PRNGKey(4), cfg)
    p["kv_norm"] = 1.0 + _normal(5, p["kv_norm"].shape, 0.1)
    x = _normal(6, (2, 32, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(32), (2, 32))
    np.testing.assert_allclose(mla.mla_apply(cfg, p, x, pos),
                               _mla_by_head(cfg, p, x), rtol=1e-4,
                               atol=1e-5)


# -- the router ---------------------------------------------------------------

def _layer(cfg, key=7):
    p = moe.moe_init(jax.random.PRNGKey(key), cfg)
    p["router"] = _normal(key + 1, p["router"].shape, 0.3)
    return p


def test_router_top_k_of_biased_scores_and_its_gates():
    cfg = SMALL
    p = _layer(cfg)
    p["router_bias"] = jnp.zeros(8).at[5].set(10.0)     # 5 always chosen
    x = _normal(9, (64, cfg.d_model))
    scores, eids, gates = moe.route_sigmoid(cfg, p, x)
    s = jax.nn.sigmoid(np.asarray(x, np.float64) @ np.asarray(
        p["router"], np.float64))
    np.testing.assert_allclose(scores, s, rtol=1e-5)
    assert (np.asarray(eids) == 5).any(-1).all()
    other = np.argsort(-np.where(np.arange(8) == 5, -1, s), -1)[:, 0]
    assert sorted(map(tuple, np.sort(eids, -1))) == sorted(
        map(tuple, np.sort(np.stack([np.full(64, 5), other], -1), -1)))
    w = np.take_along_axis(s, np.asarray(eids), -1)
    np.testing.assert_allclose(
        gates, w / w.sum(-1, keepdims=True) * cfg.routed_scaling,
        rtol=1e-5)


def test_bias_update_sign_rule():
    cfg = dataclasses.replace(SMALL, n_experts=4, bias_update_rate=0.5)
    bias = jnp.array([0.0, 1.0, -1.0, 2.0])
    loads = jnp.array([5, 1, 3, 3])                     # mean 3
    np.testing.assert_allclose(moe.bias_update(cfg, bias, loads),
                               [-0.5, 1.5, -1.0, 2.0])
    stacked = moe.bias_update(cfg, jnp.zeros((2, 4)),
                              jnp.array([[5, 1, 3, 3], [0, 0, 0, 8]]))
    np.testing.assert_allclose(stacked, [[-0.5, 0.5, 0, 0],
                                         [0.5, 0.5, 0.5, -0.5]])


def test_balance_loss_per_sequence():
    cfg = dataclasses.replace(SMALL, n_experts=4, top_k=1)
    scores = jnp.array([[[1.0, 1, 1, 1], [3, 1, 0, 0]]])      # (1, 2, 4)
    eids = jnp.array([[[0], [0]]])
    # f = E/(K·s)·count = [4, 0, 0, 0]; P_0 = mean(1/4, 3/4) = 1/2
    np.testing.assert_allclose(moe.balance_loss(cfg, scores, eids), 2.0)


# -- dropless dispatch over the held experts ----------------------------------

def _routed_by_loop(cfg, p, x, experts):
    """Σ over ``experts`` (global ids, weights p[...][i] for the i-th) of
    gate × SwiGLU over every token: no buffer, nothing dropped."""
    _, eids, gates = moe.route_sigmoid(cfg, p, x)
    y = 0.0
    for i, e in enumerate(experts):
        g = jnp.sum(jnp.where(eids == e, gates, 0.0), -1)[:, None]
        h = jax.nn.silu(x @ p["w_gate"][i]) * (x @ p["w_up"][i])
        y = y + g * (h @ p["w_down"][i])
    return y


def _shared(p, x):
    sp = p["shared"]
    return (jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])) @ sp["w_down"]


@pytest.mark.parametrize("chunk_rows", [32768, 16])
def test_dropless_dispatch_keeps_every_pair_under_skew(monkeypatch,
                                                       chunk_rows):
    """A router biased toward the held experts sends every (token, slot)
    pair here: all are computed, in one dispatch chunk or in several."""
    monkeypatch.setattr(moe, "CHUNK_ROWS", chunk_rows)
    assert moe.token_chunks(SMALL, 32) == (1 if chunk_rows > 64 else 4)
    cfg = SMALL
    p = _layer(cfg)
    p["router_bias"] = jnp.zeros(8).at[:2].set(10.0)     # held: 0 and 1
    x = _normal(11, (2, 16, cfg.d_model))
    y, _, stats = moe.moe_apply_held(cfg, p, x)
    assert int(stats["routed_here"]) == 32 * cfg.top_k
    xt = x.reshape(32, -1)
    np.testing.assert_allclose(
        y.reshape(32, -1), _routed_by_loop(cfg, p, xt, [0, 1])
        + _shared(p, xt), rtol=1e-4, atol=1e-5)


def test_megablox_kernel_path_matches_and_stays_finite(monkeypatch):
    """The chip's grouped-matmul kernel (under the Pallas interpreter,
    whose unwritten rows read NaN) against ``ragged_dot``: the same loss
    and gradients, none of them touched by the dispatch buffer's padding,
    in several chunks."""
    monkeypatch.setattr(moe, "CHUNK_ROWS", 256)
    cfg = dataclasses.replace(SMALL, compute_dtype="bfloat16", d_model=256,
                              d_ff=256, shared_d_ff=128)
    p = _layer(cfg, 51)
    x = _normal(52, (2, 128, 256)).astype(jnp.bfloat16)

    def loss(p, x):
        y, aux, _ = moe.moe_apply_held(cfg, p, x)
        return jnp.sum(jnp.square(y.astype(jnp.float32))) + aux

    got = {}
    for backend in ("interpret", "ragged_dot"):
        monkeypatch.setattr(moe, "grouped_matmul_backend", lambda: backend)
        got[backend] = jax.value_and_grad(loss, (0, 1))(p, x)
    (l1, g1), (l2, g2) = got["interpret"], got["ragged_dot"]
    np.testing.assert_allclose(l1, l2, rtol=1e-3)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, b, rtol=0.05,
                                   atol=0.02 * np.abs(b).max())


def test_routed_here_counts_the_held_pairs(monkeypatch):
    monkeypatch.setattr(moe, "CHUNK_ROWS", 16)
    cfg = dataclasses.replace(SMALL, expert_rank=2)
    p = _layer(cfg, 13)
    x = _normal(14, (2, 32, cfg.d_model))
    _, eids, _ = moe.route_sigmoid(cfg, p, x.reshape(64, -1))
    count = sum(1 for e in np.asarray(eids).reshape(-1) if 4 <= e < 6)
    _, _, stats = moe.moe_apply_held(cfg, p, x)
    assert int(stats["routed_here"]) == count
    loads = np.bincount(np.asarray(eids).reshape(-1), minlength=8)
    np.testing.assert_array_equal(stats["loads"], loads)


def test_expert_shares_sum_to_the_uncut_layer():
    """The 4 shares' partial outputs (2 of 8 experts each), with the shared
    experts counted once, add up to the uncut layer, which is the plain
    loop over all 8."""
    full_cfg = dataclasses.replace(SMALL, n_experts_held=0)
    full = _layer(full_cfg, 21)
    x = _normal(22, (2, 16, SMALL.d_model))
    xt = x.reshape(32, -1)
    y_full, _, _ = moe.moe_apply_held(full_cfg, full, x)
    np.testing.assert_allclose(
        y_full.reshape(32, -1),
        _routed_by_loop(full_cfg, full, xt, range(8)) + _shared(full, xt),
        rtol=1e-4, atol=1e-5)
    total = 0.0
    for r in range(4):
        cfg = dataclasses.replace(SMALL, expert_rank=r)
        share = dict(full, **{w: full[w][2 * r:2 * r + 2]
                              for w in ("w_gate", "w_up", "w_down")})
        total = total + moe.moe_apply_held(cfg, share, x)[0]
    total = total.reshape(32, -1) - 3 * _shared(full, xt)
    np.testing.assert_allclose(total, y_full.reshape(32, -1), rtol=1e-4,
                               atol=1e-5)


# -- the embedding's gradient -------------------------------------------------

def test_repeated_token_gradient_sums_in_float32():
    """4,096 copies of one token in a bf16-compute model: the table row's
    gradient is the float32 sum of the copies' cotangents (a bf16 sum
    drifts by about a percent)."""
    cfg = configs.get("qwen2_0_5b").reduced(compute_dtype="bfloat16")
    table = _normal(30, (cfg.vocab, cfg.d_model), 0.02)
    tokens = jnp.full((1, 4096), 7, jnp.int32)
    w = _normal(31, (1, 4096, cfg.d_model)).astype(jnp.bfloat16)

    def f(t):
        x = transformer.embed(cfg, {"tok_embed": {"table": t}}, tokens)
        return jnp.sum(x.astype(jnp.float32) * w.astype(jnp.float32))

    g = jax.grad(f)(table)
    want = np.sum(np.asarray(w, np.float64), axis=(0, 1))
    np.testing.assert_allclose(g[7], want, rtol=1e-4, atol=1e-3)
    assert float(jnp.abs(g).sum()) == pytest.approx(
        float(jnp.abs(g[7]).sum()))


def test_masked_chunked_xent_is_the_masked_mean():
    x, table = _normal(40, (2, 8, 16)), _normal(41, (32, 16))
    labels = jax.random.randint(jax.random.PRNGKey(42), (2, 8), 0, 32)
    mask = jnp.arange(8)[None].repeat(2, 0) < 7
    logits = np.asarray(x, np.float64) @ np.asarray(table, np.float64).T
    nll = np.log(np.exp(logits).sum(-1)) - np.take_along_axis(
        logits, np.asarray(labels)[..., None], -1)[..., 0]
    np.testing.assert_allclose(
        cm.chunked_softmax_xent(x, table, labels, 4, mask=mask),
        (nll * mask).sum() / mask.sum(), rtol=1e-5)


# -- the normal path ----------------------------------------------------------

def test_launcher_trains_the_share():
    from repro.launch import train
    rep = train.run(["--arch", "moonlight-16b-a3b-ep8", "--reduced",
                     "--steps", "4", "--batch", "2", "--seq", "16",
                     "--lr", "3e-3"])
    assert rep.rc == 0
    assert [b[:3] for b in rep.backends] == [("pair", "adam_rows", "xla")]
    assert all(h["routed_here"] > 0 for h in rep.history)


def test_sparse_embedding_step_refuses_a_clip():
    from repro.train.steps import make_train_step
    with pytest.raises(ValueError, match="clipping"):
        make_train_step(SMALL, grad_clip=1.0)


def test_share_config_is_the_published_widths_cut():
    full, ep8 = configs.get("moonlight-16b-a3b"), \
        configs.get("moonlight-16b-a3b-ep8")
    cut = {f.name for f in dataclasses.fields(full)
           if getattr(full, f.name) != getattr(ep8, f.name)}
    assert cut == {"name", "n_layers", "n_experts_held", "sparse_embedding",
                   "attn_chunk"}
    assert (ep8.n_layers, ep8.experts_held) == (5, 8)
    with pytest.raises(NotImplementedError):
        transformer.init_cache(ep8, 1, 16)
