"""Serving-step factory: prefill + decode per architecture family.

``decode_32k`` / ``long_500k`` cells lower ``decode_fn`` (one new token
against a ``seq_len`` cache), NOT ``train_step``.  Cache layout rules:

  * attention KV caches shard batch over the DP axes and the *sequence*
    axis over 'model' (flash-decoding: the per-shard partial max/sum of
    decode attention become cross-shard collectives);
  * recurrent SSM/RWKV state has no sequence axis — batch over DP, heads
    over 'model' (matches the TP sharding of the mixer weights).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import optimizers as opt_lib
from repro.core.optimizers import SketchHParams
from repro.distributed import sharding as shd
from repro.models.config import ArchConfig


def _family(cfg: ArchConfig):
    from repro.models import encdec, mamba, rwkv, transformer, vlm
    return {
        "gqa": transformer, "moe": transformer,
        "rwkv6": rwkv, "hybrid": mamba,
        "encdec": encdec, "vlm": vlm,
    }[cfg.family]


def cache_factory(cfg: ArchConfig) -> Callable[..., Any]:
    """(batch, max_seq) -> zeroed cache pytree for this family."""
    mod = _family(cfg)
    if cfg.family in ("gqa", "moe", "vlm"):
        from repro.models import transformer
        return lambda batch, max_seq: transformer.init_cache(cfg, batch,
                                                             max_seq)
    if cfg.family == "hybrid":
        return lambda batch, max_seq: mod.init_cache(cfg, batch, max_seq)
    if cfg.family == "rwkv6":
        def make(batch, max_seq):
            st = mod.zero_state(cfg, batch)
            st["len"] = jnp.zeros((), jnp.int32)
            return st
        return make
    if cfg.family == "encdec":
        def make(batch, max_seq):
            L, kv, hd = cfg.n_layers, cfg.n_kv, cfg.head_dim
            return {
                "k": jnp.zeros((L, batch, max_seq, kv, hd), cfg.dtype),
                "v": jnp.zeros((L, batch, max_seq, kv, hd), cfg.dtype),
                "ck": jnp.zeros((L, batch, cfg.enc_seq, cfg.n_heads, hd),
                                cfg.dtype),
                "cv": jnp.zeros((L, batch, cfg.enc_seq, cfg.n_heads, hd),
                                cfg.dtype),
                "len": jnp.zeros((), jnp.int32),
            }
        return make
    raise ValueError(cfg.family)


@dataclasses.dataclass
class ServeStep:
    cfg: ArchConfig
    prefill_fn: Callable      # (params, batch) -> (logits, cache)
    decode_fn: Callable       # (params, cache, token) -> (logits, cache)
    max_seq: int
    batch: int

    def cache_shape(self):
        factory = cache_factory(self.cfg)
        return jax.eval_shape(
            lambda: factory(batch=self.batch, max_seq=self.max_seq))

    def cache_specs(self, mesh: Mesh):
        """Heuristic spec per cache leaf: the first batch-sized dim among
        the leading dims → DP axes; then exactly ONE 'model' dim — prefer
        a sequence-length dim (KV-cache sequence parallelism), else a
        head-count dim (recurrent state TP, matching the mixer weights)."""
        cfg = self.cfg
        model = dict(zip(mesh.axis_names,
                         mesh.devices.shape)).get("model", 1)
        dp = shd.dp_axes(mesh, self.batch)
        head_sizes = set()
        if cfg.family == "rwkv6":
            head_sizes.add(cfg.rwkv_heads)
        if cfg.family == "hybrid":
            head_sizes.add(cfg.ssm_heads)

        def leaf(x):
            axes: list = [None] * x.ndim
            batch_i = next((i for i, dim in enumerate(x.shape[:3])
                            if dim == self.batch and dp), None)
            if batch_i is not None:
                axes[batch_i] = dp if len(dp) > 1 else dp[0]
            # one 'model' dim: sequence first, then heads
            cand = [i for i, dim in enumerate(x.shape)
                    if i != batch_i and dim in (self.max_seq, cfg.enc_seq)
                    and dim % model == 0 and dim > 8]
            if not cand:
                cand = [i for i, dim in enumerate(x.shape)
                        if i != batch_i and dim in head_sizes
                        and dim % model == 0]
            if cand:
                axes[cand[0]] = "model"
            while axes and axes[-1] is None:
                axes.pop()
            return P(*axes)

        spec = jax.tree_util.tree_map(leaf, self.cache_shape())
        return shd.named(mesh, spec)

    def params_shape(self):
        mod = _family(self.cfg)
        return jax.eval_shape(lambda k: mod.init(k, self.cfg),
                              jax.random.PRNGKey(0))

    def param_shardings(self, mesh: Mesh):
        ps = shd.param_specs(self.params_shape(), mesh, fsdp=self.cfg.fsdp,
                             expert_sharding=self.cfg.expert_sharding)
        return shd.named(mesh, ps)


def make_serve_step(cfg: ArchConfig, *, batch: int, max_seq: int) -> ServeStep:
    mod = _family(cfg)

    if cfg.family in ("gqa", "moe"):
        def prefill_fn(params, batch_in):
            return mod.prefill(cfg, params, batch_in["tokens"], max_seq)
    elif cfg.family == "vlm":
        def prefill_fn(params, batch_in):
            return mod.prefill(cfg, params, batch_in["patches"],
                               batch_in["tokens"], max_seq)
    elif cfg.family == "encdec":
        def prefill_fn(params, batch_in):
            return mod.prefill(cfg, params, batch_in["frames"],
                               batch_in["tokens"], max_seq)
    elif cfg.family == "rwkv6":
        def prefill_fn(params, batch_in):
            logits, state = mod.prefill(cfg, params, batch_in["tokens"],
                                        max_seq)
            state["len"] = jnp.asarray(batch_in["tokens"].shape[1], jnp.int32)
            return logits, state
    else:  # hybrid
        def prefill_fn(params, batch_in):
            return mod.prefill(cfg, params, batch_in["tokens"], max_seq)

    def decode_fn(params, cache, token):
        return mod.decode_step(cfg, params, cache, token)

    if cfg.family == "rwkv6":
        def decode_fn(params, cache, token):  # noqa: F811
            state = {k: v for k, v in cache.items() if k != "len"}
            logits, state = mod.decode_step(cfg, params, state, token)
            state["len"] = cache["len"] + 1
            return logits, state

    return ServeStep(cfg=cfg, prefill_fn=prefill_fn, decode_fn=decode_fn,
                     max_seq=max_seq, batch=batch)


# sentinel: "the caller did not choose a dir_clip" — distinguishable from
# an explicit 10.0 (or None), so the single-device path can reject dp-only
# arguments instead of silently ignoring them
_DIR_CLIP_DEFAULT = object()


def make_online_adapt_step(n_rows: int, dim: int, *, lr=1e-4,
                           b2: float = 0.999, eps: float = 1e-8,
                           hparams: Optional[SketchHParams] = None,
                           path: str = "serve_adapt",
                           v_store=None,
                           store_backend: Optional[str] = None,
                           dp_axis: Optional[str] = None,
                           mesh: Optional[Mesh] = None,
                           error_feedback: bool = False,
                           dir_clip=_DIR_CLIP_DEFAULT):
    """Serve-time sparse adaptation of an embedding table.

    Serving workloads that personalize online (session embeddings, bandit
    heads, retrieval tables) update a handful of rows per decode batch.
    This is exactly the sparse-rows regime: the auxiliary state lives in a
    count-min sketch — a few MB instead of a second table — and the step
    routes through the same kernel-backend registry as training
    (``repro.kernels``; tiled Pallas pipeline on TPU).

    Uses the β₁=0 (Theorem 5.1 / RMSProp) variant: no first moment, which
    keeps serve-time state minimal and matches the paper's extreme-scale
    configuration.  ``v_store``: an optional bound ``CountMinStore``
    (e.g. resolved from a planner ``StoreTree``) superseding the
    ``hparams`` sizing — serve-time adaptation speaks the same store
    vocabulary as training (DESIGN.md §12).  ``store_backend`` pins the
    kernel backend (DESIGN.md §14), overriding both ``hparams.backend``
    and whatever backend the ``v_store`` carries — serving fleets can
    force e.g. 'xla' on CPU hosts while training runs 'tiled'.

    ``dp_axis``: replicated serving fleets adapt the SAME table from
    per-replica feedback shards — ``adapt_fn`` becomes a ``shard_map``
    over that axis of ``mesh`` (or the active mesh at trace time) whose
    collective all-reduces the (depth, width, dim) 2nd-moment gradient
    sketch plus the int32 ids instead of the (k, d) rows, keeping every
    replica's table and sketch state identical (DESIGN.md §13).

    Returns ``(init_state_fn, adapt_fn)``:

        opt_state          = init_state_fn()
        table', opt_state' = adapt_fn(table, opt_state, ids, grad_rows)
    """
    hp = hparams if hparams is not None else SketchHParams()
    if store_backend is not None:
        hp = dataclasses.replace(hp, backend=store_backend)
        if v_store is not None:
            v_store = dataclasses.replace(v_store, backend=store_backend)
    if dp_axis is None:
        # error_feedback / dir_clip only exist on the DP reduction path
        # (sketched all-reduce residual + trust clamp); silently ignoring
        # them here would let a fleet think it runs with stability guards
        # it doesn't have
        if error_feedback:
            raise ValueError(
                "error_feedback=True needs dp_axis: the residual sketch "
                "accumulates the CROSS-REPLICA 2nd-moment term of the "
                "sketched all-reduce (DESIGN.md §13) — a single-device "
                "adapt step has no such term")
        if dir_clip is not _DIR_CLIP_DEFAULT:
            raise ValueError(
                "dir_clip only applies to the dp_axis path (it trust-"
                "clamps the direction against sketched-reduce estimator "
                "noise); the single-device step would silently ignore "
                "it — drop the argument or set dp_axis")
        opt = opt_lib.sparse_rows_adam(
            lr, b2=b2, eps=eps, shape=(n_rows, dim), path=path, hparams=hp,
            track_first_moment=False, v_store=v_store)
    else:
        if dir_clip is _DIR_CLIP_DEFAULT:
            dir_clip = 10.0
        opt = opt_lib.sparse_rows_adam_dp(
            lr, b2=b2, eps=eps, shape=(n_rows, dim), path=path,
            axis_name=dp_axis, hparams=hp, track_first_moment=False,
            error_feedback=error_feedback, dir_clip=dir_clip,
            v_store=v_store)

    def init_state_fn():
        return opt.init()

    def local_adapt(table, opt_state, ids, grad_rows):
        updates, opt_state = opt.update(
            {"ids": ids, "rows": grad_rows}, opt_state)
        return opt_lib.apply_sparse_updates(table, updates), opt_state

    if dp_axis is None:
        return init_state_fn, local_adapt
    return init_state_fn, shd.dp_sparse_wrap(local_adapt, mesh=mesh,
                                             dp_axis=dp_axis)


def make_dense_adapt_step(n_rows: int, dim: int, *, lr=1e-4,
                          b2: float = 0.999, eps: float = 1e-8):
    """Dense-baseline sibling of ``make_online_adapt_step``: the β₁=0
    update rule with a FULL (n, d) 2nd-moment buffer instead of a
    count-min sketch — the memory the sketch arm frees.  Same
    ``(init_state_fn, adapt_fn)`` contract and (ids, grad_rows) calling
    convention (``dense_rows_adam`` under the hood, so per-step work is
    still O(touched rows)); the serving benchmark replays the same
    traffic trace against both arms."""
    from repro.train.extreme import dense_rows_adam
    opt = dense_rows_adam(lr, b1=0.0, b2=b2, eps=eps, shape=(n_rows, dim))

    def init_state_fn():
        return opt.init()

    def adapt_fn(table, opt_state, ids, grad_rows):
        updates, opt_state = opt.update(
            {"ids": ids, "rows": grad_rows}, opt_state)
        return opt_lib.apply_sparse_updates(table, updates), opt_state

    return init_state_fn, adapt_fn


def timed_adapt(adapt_fn, tracker=None, *, capacity: int = 4096):
    """Wrap an ``adapt_fn`` with serve-latency telemetry (DESIGN.md §15).

    Returns ``(wrapped_adapt_fn, tracker)``: each call runs under a
    ``jax.profiler.TraceAnnotation`` span, blocks on BOTH the returned
    table and the optimizer state (the sketch write is the bulk of the
    step's work — blocking on the table alone records a latency that
    excludes it), and records wall time into an ``obs.LatencyTracker``.

        adapt, lat = timed_adapt(adapt_fn)
        ...
        writer.write("serve", adapt_ms=lat.summary(),
                     reads_per_s=lat.per_second())

    ``tracker`` lets a fleet share one histogram across tables; by
    default each wrapper gets its own ``capacity``-sample window."""
    import time

    import jax

    from repro.obs.profiling import LatencyTracker
    lat = tracker if tracker is not None else LatencyTracker(capacity)

    def wrapped(table, opt_state, ids, grad_rows):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("obs.adapt"):
            table, opt_state = adapt_fn(table, opt_state, ids, grad_rows)
            jax.block_until_ready((table, opt_state))
        lat.record(time.perf_counter() - t0)
        return table, opt_state

    return wrapped, lat
