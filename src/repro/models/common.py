"""Shared model building blocks — param-dict pure functions, no framework.

Conventions
-----------
* Params are nested dicts of ``jnp.ndarray`` leaves.  Layer stacks store
  leaves with a leading ``n_layers`` axis and are driven by ``lax.scan``
  (one trace per layer family — compile-time economy for the dry-run).
* Embedding and vocab-projection tables are **vocab-major** ``(vocab, d)``
  so the count-sketch optimizer hashes rows (= classes/features), matching
  the paper.
* Mixed precision: master params fp32; ``cast(params, cfg.compute_dtype)``
  at the top of each forward; losses/softmax in fp32.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


def shard_act(x, *rest):
    """Megatron-style activation sharding constraint: batch over the DP
    axes ('pod','data'), remaining dims per ``rest`` ('model' / None).
    No-op outside an ``active_mesh`` context; axes that don't exist or
    don't divide are dropped automatically — one call site serves every
    (arch × mesh) cell.  Without these constraints GSPMD picks
    inconsistent intermediate shardings and reshards full activations
    many times per layer (measured ~20 (b,s,d)-sized collectives/layer on
    yi-9b before constraints; see EXPERIMENTS.md §Perf)."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import constraint
    return constraint(x, P(("pod", "data"), *rest))


def cast_tree(tree, dtype):
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree)


def dense_init(key, d_in: int, d_out: int, scale: Optional[float] = None,
               dtype=jnp.float32) -> jnp.ndarray:
    scale = scale if scale is not None else (1.0 / jnp.sqrt(d_in))
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def layernorm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
              eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 10000.0) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32.

    Angles/cos/sin are computed in f32 (position precision) but the
    rotation multiplies in the INPUT dtype — standard bf16 practice; also
    tested as a collective-dtype fix in §Perf internlm2 iteration 2
    (refuted: the f32 boundary collectives come from the rmsnorm product,
    not rope)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                     # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(ang)[..., None, :].astype(x.dtype)  # (..., seq, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def sinusoidal_positions(seq: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, jnp.float32) * (-jnp.log(10000.0) / d))
    pe = jnp.zeros((seq, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray,
                 mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean token cross-entropy in fp32.  logits (..., V), labels (...)"""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def chunked_softmax_xent(x: jnp.ndarray, table: jnp.ndarray,
                         labels: jnp.ndarray, chunk: int = 512,
                         mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Full-softmax mean token xent WITHOUT materializing (b·s, V) logits.

    Scans over SEQUENCE chunks (the scan axis must be unsharded: chunking
    over flattened b·s rows breaks the (data, model) merged-dim sharding
    and GSPMD all-gathers the entire fp32 activation tensor — a 17 GiB
    buffer at the yi-9b train_4k cell, see EXPERIMENTS.md §Perf).  Per
    chunk: all-gather the s-slice over 'model' (Megatron-SP pattern),
    matmul against the vocab-sharded table so logits shard on V, reduce.
    ``jax.checkpoint`` on the body recomputes chunk logits in the
    backward.  x (b, s, d); table (V, d) [vocab-sharded]; labels (b, s);
    ``mask`` (b, s), where given: the mean over the positions it weights.
    """
    b, s, d = x.shape
    if s % chunk != 0:
        chunk = s
    nc = s // chunk

    def body(acc, xs):
        xc, lc = xs[:2]                              # (b, chunk, d), (b, chunk)
        xc = shard_act(xc, None, None)               # gather s over 'model'
        # the f32 table straight into the matmul: no half-precision copy
        # of it is materialised (the chip multiplies f32 operands at the
        # default precision in bf16 passes), and its gradient comes out
        # in f32
        logits = jnp.einsum("bcd,vd->bcv", xc, table,
                            preferred_element_type=jnp.float32)
        logits = shard_act(logits, None, "model")
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        nll = logz - gold
        if mask is not None:
            nll = nll * xs[2]
        return acc + jnp.sum(nll), None

    body = jax.checkpoint(body, prevent_cse=False)
    xs = (jnp.moveaxis(x.reshape(b, nc, chunk, d), 1, 0),
          jnp.moveaxis(labels.reshape(b, nc, chunk), 1, 0))
    if mask is not None:
        xs += (jnp.moveaxis(mask.astype(jnp.float32).reshape(b, nc, chunk),
                            1, 0),)
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), xs)
    if mask is not None:
        return total / jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
    return total / (b * s)


def sampled_softmax_xent(x: jnp.ndarray, table: jnp.ndarray,
                         labels: jnp.ndarray, sample_ids: jnp.ndarray,
                         mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Sampled softmax (paper §7.2, Jean et al. 2014).

    x: (T, d) final hidden; table: (V, d) output embedding; labels: (T,);
    sample_ids: (S,) negative class ids (shared across the batch, the
    standard trick).  Computes logits only over {labels} ∪ {samples} so the
    softmax-layer gradient is row-sparse — the regime the count-sketch
    optimizer exploits."""
    x = x.astype(jnp.float32)
    pos_rows = table[labels].astype(jnp.float32)         # (T, d)
    neg_rows = table[sample_ids].astype(jnp.float32)     # (S, d)
    pos_logit = jnp.sum(x * pos_rows, axis=-1)           # (T,)
    neg_logits = x @ neg_rows.T                          # (T, S)
    # remove accidental hits (negatives equal to the label)
    hit = (sample_ids[None, :] == labels[:, None])
    neg_logits = jnp.where(hit, -1e9, neg_logits)
    logz = jax.nn.logsumexp(
        jnp.concatenate([pos_logit[:, None], neg_logits], axis=-1), axis=-1)
    nll = logz - pos_logit
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
