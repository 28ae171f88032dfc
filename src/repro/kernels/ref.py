"""Pure-jnp oracles for the Pallas sketch kernels.

All functions take pre-computed hash ``buckets``/``signs`` (from
``repro.core.hashing.HashFamily``) so the kernel and the oracle are fed
bit-identical addressing.  Two semantics exist (see core/sketch.py):

  * batch     — query sees the pre-step sketch; scatter-adds accumulate.
                ``cs_query_ref`` / ``cs_update_ref``: the tiled kernels'
                semantics within one tile.
  * streaming — rows are processed one at a time, later rows see earlier
                rows' updates.  This is the paper's exact per-item
                algorithm; ``adam_fused_ref`` reproduces it with a
                ``lax.scan`` (the 'ref' pair backend).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.sketch import median_rows


def _median_depth(vals: jnp.ndarray) -> jnp.ndarray:
    return median_rows([vals[i] for i in range(vals.shape[0])])


def cs_query_ref(S: jnp.ndarray, buckets: jnp.ndarray,
                 signs: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Batch QUERY.  S (v,w,d); buckets (v,k) int32; signs (v,k) or None
    (None => Count-Min: min-estimator).  Returns (k, d)."""
    gathered = jax.vmap(lambda Sj, bj: Sj[bj])(S, buckets)  # (v,k,d)
    if signs is None:
        return jnp.min(gathered, axis=0)
    return _median_depth(gathered * signs[..., None].astype(S.dtype))


def cs_update_ref(S: jnp.ndarray, buckets: jnp.ndarray,
                  signs: Optional[jnp.ndarray],
                  delta: jnp.ndarray) -> jnp.ndarray:
    """Batch UPDATE (scatter-add).  delta (k, d).  Returns new S."""
    if signs is None:
        upd = jnp.broadcast_to(delta[None].astype(S.dtype),
                               (S.shape[0],) + delta.shape)
    else:
        upd = signs[..., None].astype(S.dtype) * delta[None].astype(S.dtype)
    return jax.vmap(lambda Sj, bj, uj: Sj.at[bj].add(uj))(S, buckets, upd)


def adam_fused_ref(M: Optional[jnp.ndarray], V: jnp.ndarray,
                   bm: Optional[jnp.ndarray], sm: Optional[jnp.ndarray],
                   bv: jnp.ndarray, g: jnp.ndarray, *,
                   lr: float, b1: float, b2: float, eps: float,
                   bc1: float, bc2: float
                   ) -> Tuple[Optional[jnp.ndarray], jnp.ndarray, jnp.ndarray]:
    """Streaming CS-Adam (paper Alg. 4 applied row by row).

    M: count-sketch of the 1st moment (signed) or None for the β₁=0 variant.
    V: count-min sketch of the 2nd moment (unsigned).
    bm/sm: (v,k) buckets+signs for M;  bv: (v,k) buckets for V.
    g: (k, d) gradient rows.  Returns (M', V', param_updates (k,d)).
    """
    vdepth = V.shape[0]
    track_m = M is not None

    def row(carry, xs):
        Mc, Vc = carry
        if track_m:
            bm_i, sm_i, bv_i, g_i = xs
        else:
            bv_i, g_i = xs
        # --- 1st moment ---------------------------------------------------
        if track_m:
            vals = Mc[jnp.arange(vdepth), bm_i]          # (v, d)
            vals = vals * sm_i[:, None]
            m_old = _median_depth(vals)
            dm = (1.0 - b1) * (g_i - m_old)
            Mc = Mc.at[jnp.arange(vdepth), bm_i].add(sm_i[:, None] * dm[None])
            m_new = m_old + dm
            mhat = m_new / bc1
        else:
            mhat = g_i
        # --- 2nd moment ---------------------------------------------------
        v_old = jnp.min(Vc[jnp.arange(vdepth), bv_i], axis=0)
        dv = (1.0 - b2) * (g_i * g_i - v_old)
        Vc = Vc.at[jnp.arange(vdepth), bv_i].add(
            jnp.broadcast_to(dv[None], (vdepth,) + dv.shape))
        v_new = jnp.maximum(v_old + dv, 0.0)
        vhat = v_new / bc2
        upd = -lr * mhat / (jnp.sqrt(vhat) + eps)
        return (Mc, Vc), upd

    xs = (bm.T, sm.T, bv.T, g) if track_m else (bv.T, g)
    carry0 = (M, V) if track_m else (V, V)  # first slot unused when β₁=0
    (M_out, V_out), upds = jax.lax.scan(row, carry0, xs)
    return (M_out if track_m else None), V_out, upds
