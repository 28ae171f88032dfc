"""Plain reference of the sparse-rows embedding cells: count-sketch Adam
(the paper's Algorithm 4) over an embedding table pulled toward a target.

Per step, on the step's ids (duplicates allowed):

    loss   = mean over ids and columns of (table[id] − target[id])²
    g_u    = Σ over the occurrences of id u of (table[u] − target[u])
    then, one unique id after another in ascending order (the paper's
    per-item order), with M the signed first-moment sketch and V the
    count-min second-moment sketch:
        m_old = median_j s_j(u)·M[j, h_j(u)]       v_old = min_j V[j, h_j(u)]
        dm = (1−β₁)(g_u − m_old)                    dv = (1−β₂)(g_u² − v_old)
        M[j, h_j(u)] += s_j(u)·dm                   V[j, h_j(u)] += dv
        d_u = ((m_old+dm)/(1−β₁ᵗ)) / (sqrt(max(v_old+dv, 0)/(1−β₂ᵗ)) + ε)
    table[u] −= lr · clip(d_u, ±dir_clip)

in float32 (``control`` lowers every array and operation).
The hash family is ``countsketch``'s, at the configuration's (seed, depth,
width).  The table and the target start from the benchmark's draw for the
seed (``chipbench.weights`` streams 0 and 1)."""
from __future__ import annotations

import functools
from typing import Dict, List

import numpy as np

from chipbench import weights
from chipbench.reference import countsketch as cs

PAD = 4096


def _stream(M, V, table, bm, sm, uids, g, live, t, *, lr, b1, b2, eps, clip):
    import jax
    import jax.numpy as jnp
    dt = M.dtype
    depth = M.shape[0]
    rows_j = jnp.arange(depth)
    bc1 = (1 - jnp.asarray(b1, jnp.float32) ** t).astype(dt)
    bc2 = (1 - jnp.asarray(b2, jnp.float32) ** t).astype(dt)
    c1, c2 = jnp.asarray(1 - b1, dt), jnp.asarray(1 - b2, dt)

    def row(carry, xs):
        M, V, table = carry
        b, s, u, gi, ok = xs
        m_old = jnp.median(M[rows_j, b] * s[:, None], axis=0)
        dm = c1 * (gi - m_old) * ok
        M = M.at[rows_j, b].add(s[:, None] * dm[None])
        v_old = jnp.min(V[rows_j, b], axis=0)
        dv = c2 * (gi * gi - v_old) * ok
        V = V.at[rows_j, b].add(jnp.broadcast_to(dv[None], (depth,) + dv.shape))
        mhat = (m_old + dm) / bc1
        vhat = jnp.maximum(v_old + dv, 0) / bc2
        d = jnp.clip(mhat / (jnp.sqrt(vhat) + jnp.asarray(eps, dt)),
                     -clip, clip)
        table = table.at[u].add((-lr * d * ok).astype(dt))
        return (M, V, table), None

    (M, V, table), _ = jax.lax.scan(
        row, (M, V, table), (bm.T, sm.T, uids, g, live))
    return M, V, table


@functools.lru_cache(maxsize=4)
def _compiled_stream(lr, b1, b2, eps, clip):
    import jax
    return jax.jit(functools.partial(_stream, lr=lr, b1=b1, b2=b2, eps=eps,
                                     clip=clip), donate_argnums=(0, 1, 2))


def numbers(cell, seed: int, batches: List[Dict], control=None) -> dict:
    """The reference's readings over the first three ``batches``: each
    step's loss, the first gradient from the state after step 1, the
    table's change after step 3.  ``control`` names a lower precision
    (``bfloat16``) that every array and operation takes instead of float32."""
    import jax.numpy as jnp
    dtype = jnp.dtype(control or "float32")
    cfg = cell.config
    n_rows, dim = int(cfg["num_rows"]), int(cfg["embedding_dim"])
    sk, opt = cfg["sketch"], cfg["optimizer"]
    depth, width, hseed = int(sk["depth"]), int(sk["width"]), int(sk["seed"])
    b1, b2, eps = 0.9, 0.999, 1e-8
    scale = float(dim) ** -0.5
    table = weights.draw(weights.seed_key(seed, 0), shape=(n_rows, dim),
                         scale=scale).astype(dtype)
    target = weights.draw(weights.seed_key(seed, 1), shape=(n_rows, dim),
                          scale=scale).astype(dtype)
    M = jnp.zeros((depth, width, dim), dtype)
    V = jnp.zeros((depth, width, dim), dtype)
    step = _compiled_stream(float(opt["lr"]), b1, b2, eps,
                            float(opt["dir_clip"]))
    losses, grad = [], {}
    leaf_grad = {}
    steps = [np.asarray(b["tokens"]).reshape(-1).astype(np.int64)
             for b in batches[:3]]
    uniq = [np.unique(ids, return_counts=True) for ids in steps]
    # the stream runs over the unique ids, padded to one length for the
    # three steps (a multiple of PAD, so that few lengths ever compile)
    k = -(-max(u.size for u, _ in uniq) // PAD) * PAD
    for t, (ids, (uids, counts)) in enumerate(zip(steps, uniq), start=1):
        n_u = uids.size
        pad_ids = np.zeros(k, np.int64)
        pad_ids[:n_u] = uids
        live = np.zeros(k, np.float32)
        live[:n_u] = 1.0
        cnt = np.zeros(k, np.float32)
        cnt[:n_u] = counts
        jids = jnp.asarray(ids, jnp.int32)
        diff = (table[jids] - target[jids]).astype(jnp.float32)
        losses.append(float(jnp.mean(jnp.square(diff))))
        ju = jnp.asarray(pad_ids, jnp.int32)
        g = (table[ju] - target[ju]) * jnp.asarray(cnt, dtype)[:, None]
        if t == 1:
            leaf_grad["table"] = float(jnp.sqrt(jnp.sum(
                jnp.square(g.astype(jnp.float32)))))
        bm = cs.buckets(hseed, depth, width, ju)
        sm = cs.signs(hseed, depth, ju).astype(dtype)
        M, V, table = step(M, V, table, bm, sm, ju, g,
                           jnp.asarray(live, dtype)[:, None].astype(dtype),
                           jnp.asarray(t, jnp.float32))
        if t == 1:
            grad = {"m": {"table": float(jnp.sqrt(jnp.sum(jnp.square(
                        M.astype(jnp.float32))))) / (1 - b1)},
                    "v": {"table": float(jnp.sqrt(jnp.sum(jnp.square(
                        V.astype(jnp.float32))))) / (1 - b2)}}
    change = {"table": float(weights.change_sq(
        table, weights.seed_key(seed, 0), scale=scale)) ** 0.5}
    for x in (M, V, table, target):
        x.delete()
    return {"loss": losses, "grad": grad, "change": change,
            "leaf_grad": leaf_grad}
