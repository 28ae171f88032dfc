"""Moonlight-16B-A3B — the DeepSeek-V3 architecture
[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3].

27L (first_k_dense_replace 1: one dense layer, then 26 MoE layers)
d_model=2048  16H latent attention (kv_lora_rank 512, q_lora_rank null,
qk_nope 128 + qk_rope 64, v 128, rope_theta 50,000)  dense d_ff=11264
64 routed experts of d_ff 1408, 6 a token, sigmoid scores with the
aux-loss-free bias (noaux_tc, n_group 1), norm_topk_prob, scaling 2.446,
sequence-wise balance loss; 2 shared experts merged into one 2816-wide
SwiGLU; vocab 163,840 untied; rms_norm_eps 1e-5.  γ (bias update rate)
and α (balance-loss weight) are DeepSeek-V3's, the config gives neither.

``EP8`` is one chip's share of an 8-chip expert-parallel deployment
(``ep_share``).
"""
import dataclasses

from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv=16, head_dim=192,
    d_ff=1408, vocab_size=163840, rope_theta=50000.0, norm_eps=1e-5,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    n_experts=64, top_k=6, shared_d_ff=2 * 1408, expert_sharding="ep",
    first_k_dense=1, dense_d_ff=11264,
    router="sigmoid", routed_scaling=2.446, bias_update_rate=1e-3,
    aux_loss_alpha=1e-4,
)


def ep_share(cfg: ArchConfig, *, chips: int, rank: int = 0,
             moe_layers: int = 4, **execution) -> ArchConfig:
    """What one chip of a ``chips``-way expert-parallel deployment trains:
    each MoE layer's experts split over the chips (this one holds
    ``n_experts // chips``, share ``rank``), which are also the data-
    parallel ranks of everything else, and the depth cut to the leading
    dense layers plus ``moe_layers`` MoE layers (the rest would lie on
    further pipeline stages).  Widths, the router's outputs and the
    experts per token stay as published; the vocabulary tables are whole
    and train through the sparse-rows step.  ``execution`` sets knobs
    that change no result's meaning (chunk sizes)."""
    if cfg.n_experts % chips:
        raise ValueError(f"{cfg.n_experts} experts do not split over "
                         f"{chips} chips")
    return dataclasses.replace(
        cfg, name=f"{cfg.name}-ep{chips}",
        n_layers=cfg.first_k_dense + moe_layers,
        n_experts_held=cfg.n_experts // chips, expert_rank=rank,
        sparse_embedding=True, **execution)


EP8 = ep_share(CONFIG, chips=8, attn_chunk=256)
