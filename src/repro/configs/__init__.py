"""Assigned-architecture registry + per-cell input specs.

    from repro.configs import get, REGISTRY, input_specs, cell_skip

Every ``<arch>.py`` module defines ``CONFIG`` (exact public dims) — see
each file's ``[source]`` note.  ``input_specs(cfg, shape)`` returns the
ShapeDtypeStruct stand-ins the dry-run lowers against (weak-type-correct,
no allocation).  ``cell_skip`` encodes the assignment's shape-skip rules
(long_500k only for sub-quadratic archs).
"""
from __future__ import annotations

import importlib
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig, ShapeConfig, SHAPES  # noqa: F401

ARCH_IDS = (
    "internlm2_20b",
    "yi_9b",
    "granite_20b",
    "qwen2_0_5b",
    "rwkv6_7b",
    "whisper_medium",
    "internvl2_2b",
    "zamba2_2_7b",
    "qwen2_moe_a2_7b",
    "llama4_maverick_400b_a17b",
)

_ALIASES = {
    "internlm2-20b": "internlm2_20b",
    "yi-9b": "yi_9b",
    "granite-20b": "granite_20b",
    "qwen2-0.5b": "qwen2_0_5b",
    "rwkv6-7b": "rwkv6_7b",
    "whisper-medium": "whisper_medium",
    "internvl2-2b": "internvl2_2b",
    "zamba2-2.7b": "zamba2_2_7b",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "llama4-maverick-400b-a17b": "llama4_maverick_400b_a17b",
    # trained at one chip's share only: not in ARCH_IDS' dry-run matrix
    "moonlight-16b-a3b": "moonlight_16b_a3b",
    "moonlight-16b-a3b-ep8": "moonlight_16b_a3b:EP8",
}


def get(arch: str) -> ArchConfig:
    """The config named ``arch``; an alias may name ``module:ATTR``."""
    name, _, attr = _ALIASES.get(arch, arch).partition(":")
    mod_name = name.replace("-", "_").replace(".", "_")
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    return getattr(mod, attr or "CONFIG")


def registry() -> Dict[str, ArchConfig]:
    return {a: get(a) for a in ARCH_IDS}


# ---------------------------------------------------------------------------
# Cell matrix (arch × shape) skip rules
# ---------------------------------------------------------------------------

SUBQUADRATIC = {"rwkv6_7b", "zamba2_2_7b"}


def cell_skip(arch: str, shape: str) -> Optional[str]:
    """None if the cell runs; otherwise the reason it is skipped."""
    arch = _ALIASES.get(arch, arch)
    if shape == "long_500k" and arch not in SUBQUADRATIC:
        return ("long_500k needs sub-quadratic attention; "
                f"{arch} is full-attention (DESIGN.md §6)")
    return None


def cells():
    """All effective (arch, shape) pairs."""
    for a in ARCH_IDS:
        for s in SHAPES:
            if cell_skip(a, s) is None:
                yield a, s


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins, no allocation)
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def train_batch_specs(cfg: ArchConfig, shape: ShapeConfig,
                      sampled_softmax: bool = False) -> Dict:
    """The kwargs pytree for train_step's ``batch`` argument."""
    B, S = shape.global_batch, shape.seq_len
    batch = {}
    if sampled_softmax:
        batch["neg_ids"] = _sds((cfg.softmax_samples,), jnp.int32)
    if cfg.family == "encdec":
        batch["frames"] = _sds((B, cfg.enc_seq, cfg.d_model), cfg.compute_dtype)
    if cfg.family == "vlm":
        batch["patches"] = _sds((B, cfg.n_patches, cfg.d_model),
                                cfg.compute_dtype)
        S = S - cfg.n_patches        # total positions == the cell's seq_len
    batch["tokens"] = _sds((B, S), jnp.int32)
    batch["labels"] = _sds((B, S), jnp.int32)
    return batch


def prefill_batch_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict:
    B, S = shape.global_batch, shape.seq_len
    batch = {}
    if cfg.family == "encdec":
        batch["frames"] = _sds((B, cfg.enc_seq, cfg.d_model), cfg.compute_dtype)
    if cfg.family == "vlm":
        batch["patches"] = _sds((B, cfg.n_patches, cfg.d_model),
                                cfg.compute_dtype)
        S = S - cfg.n_patches
    batch["tokens"] = _sds((B, S), jnp.int32)
    return batch


def decode_cache_specs(cfg: ArchConfig, shape: ShapeConfig):
    """eval_shape the family's init_cache — zero allocation."""
    from repro.serve.steps import cache_factory
    factory = cache_factory(cfg)
    return jax.eval_shape(
        lambda: factory(batch=shape.global_batch, max_seq=shape.seq_len))


def decode_batch_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict:
    return {"token": _sds((shape.global_batch,), jnp.int32)}


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict:
    if shape.kind == "train":
        return train_batch_specs(cfg, shape)
    if shape.kind == "prefill":
        return prefill_batch_specs(cfg, shape)
    return decode_batch_specs(cfg, shape)
