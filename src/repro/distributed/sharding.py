"""Logical-axis sharding rules with automatic divisibility fallback.

The framework shards by *path pattern*: every parameter leaf is matched
against a rule table mapping it to a tuple of mesh-axis names (or None)
per dimension.  Two safety valves make the same rules valid for every
(arch × mesh) cell:

  * **missing axes drop out** — a rule may name "pod"; on the single-pod
    mesh that axis doesn't exist and is treated as None;
  * **divisibility fallback** — if a dim is not divisible by the named
    axis size the axis is dropped for that dim (e.g. qwen2-0.5b's 14
    heads on a 16-way 'model' axis ⇒ its attention weights replicate).

Layer-stacked leaves (under ``layers/``) get an implicit leading None for
the ``lax.scan`` axis.

ZeRO-1: dense optimizer moments take the parameter's spec plus 'data'
sharding on the first still-unsharded divisible dim.  Sketch tensors
``(depth, width, dim)`` shard width over 'data' and dim over 'model'.
FSDP (llama4-maverick): master weights additionally shard their d_ff/
d_model dims over 'data'/'pod'; GSPMD inserts the per-layer all-gathers.
"""
from __future__ import annotations

import re
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# ---------------------------------------------------------------------------
# Rule table: (path regex, per-dim axis template)
# Templates name mesh axes; 'fsdp:<axis>' entries apply only when the
# config opts into fsdp.  Matched against the path *suffix*.
# ---------------------------------------------------------------------------

RULES: Sequence[Tuple[str, Tuple[Any, ...]]] = (
    # --- vocab tables: row(vocab)-sharded over model (Megatron vocab-parallel)
    (r"(tok_embed|lm_head)/table$", ("model", "fsdp:data")),
    # --- attention ---------------------------------------------------------
    (r"attn/wq$", (None, "model")),
    (r"attn/wk$", (None, "model")),
    (r"attn/wv$", (None, "model")),
    (r"attn/wo$", ("model", None)),
    (r"attn/b[qkv]$", ("model",)),
    (r"(self_attn|cross_attn)/wq$", (None, "model")),
    (r"(self_attn|cross_attn)/wk$", (None, "model")),
    (r"(self_attn|cross_attn)/wv$", (None, "model")),
    (r"(self_attn|cross_attn)/wo$", ("model", None)),
    # --- dense FFN ----------------------------------------------------------
    (r"ffn/w_gate$", (None, "model")),
    (r"ffn/w_up$", (None, "model")),
    (r"ffn/w_down$", ("model", None)),
    (r"mlp/w1$", (None, "model")),
    (r"mlp/w2$", ("model", None)),
    # --- MoE (expert_sharding='ep'); 'tp' override handled in spec_for ------
    (r"ffn/router$", (None, None)),
    (r"ffn/w_gate3$", ("model", "fsdp:pod", "fsdp:data")),   # (E, d, f)
    (r"ffn/w_up3$", ("model", "fsdp:pod", "fsdp:data")),
    (r"ffn/w_down3$", ("model", "fsdp:data", "fsdp:pod")),   # (E, f, d)
    (r"ffn/shared/w_gate$", (None, "model")),
    (r"ffn/shared/w_up$", (None, "model")),
    (r"ffn/shared/w_down$", ("model", None)),
    # --- RWKV6 ---------------------------------------------------------------
    (r"tm/w[rkvg]$", (None, "model")),
    (r"tm/wo$", ("model", None)),
    (r"tm/w_[AB]$", (None, None)),
    (r"tm/u$", (None, None)),
    (r"cm/wk$", (None, "model")),
    (r"cm/wv$", ("model", None)),
    (r"cm/wr$", (None, "model")),
    # --- Mamba2 --------------------------------------------------------------
    (r"[zx]_proj$", (None, "model")),    # (d, d_inner) — head-sharded
    (r"bc_proj$", (None, None)),         # (d, 2n): n is tiny, replicate
    (r"dt_proj$", (None, "model")),      # (d, heads)
    (r"conv_w_x$", (None, "model")),     # (K, di) depthwise — channel-sharded
    (r"conv_b_x$", ("model",)),
    (r"conv_w_bc$", (None, None)),
    (r"conv_b_bc$", (None,)),
    (r"out_proj$", ("model", None)),     # (d_inner, d)
    (r"(A_log|dt_bias|D)$", ("model",)),  # per-head scalars
    (r"gn$", ("model",)),                # group-norm scale over d_inner
)

_REPLICATE = re.compile(r"(ln\d?|norm|scale|bias|mix_|w_base|router)")


def _axis_size(mesh: Mesh, name: str) -> Optional[int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name)


def _resolve_dim(entry, dim: int, mesh: Mesh, fsdp: bool):
    """Template entry -> mesh axis name or None (with fallbacks)."""
    if entry is None:
        return None
    if isinstance(entry, str) and entry.startswith("fsdp:"):
        if not fsdp:
            return None
        entry = entry.split(":", 1)[1]
    size = _axis_size(mesh, entry)
    if size is None or dim % size != 0:
        return None
    return entry


def spec_for(path: str, shape: Tuple[int, ...], mesh: Mesh, *,
             fsdp: bool = False, expert_sharding: str = "ep") -> P:
    """PartitionSpec for one parameter leaf."""
    if _REPLICATE.search(path.rsplit("/", 1)[-1]) and "proj" not in path:
        return P()
    stacked = "/layers/" in f"/{path}" or path.startswith(("layers/",
                                                           "enc_layers/",
                                                           "dec_layers/"))
    for pat, template in RULES:
        if re.search(pat, path):
            tpl = template
            # MoE rank-3 leaves carry a '3' marker in the rule table; the
            # actual param paths are ffn/w_gate etc. with ndim==3(+stack).
            break
    else:
        tpl = None
    ndim = len(shape)
    eff_shape = shape[1:] if stacked else shape
    if tpl is None or len(tpl) != len(eff_shape):
        # rank-3 MoE leaves match the rank-2 ffn rules by name; redirect
        if re.search(r"ffn/w_(gate|up|down)$", path) and len(eff_shape) == 3:
            name = path.rsplit("/", 1)[-1]
            if expert_sharding == "ep":
                tpl = dict(w_gate=("model", "fsdp:pod", "fsdp:data"),
                           w_up=("model", "fsdp:pod", "fsdp:data"),
                           w_down=("model", "fsdp:data", "fsdp:pod"))[name]
            else:  # per-expert TP on d_ff
                tpl = dict(w_gate=(None, None, "model"),
                           w_up=(None, None, "model"),
                           w_down=(None, "model", None))[name]
        else:
            tpl = (None,) * len(eff_shape)
    axes = [
        _resolve_dim(entry, dim, mesh, fsdp)
        for entry, dim in zip(tpl, eff_shape)
    ]
    if stacked:
        axes = [None] + axes
    while axes and axes[-1] is None:
        axes.pop()
    return P(*axes)


# ---------------------------------------------------------------------------
# Tree-level helpers
# ---------------------------------------------------------------------------

def _iter_with_path(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    for kp, leaf in flat:
        parts = []
        for k in kp:
            if hasattr(k, "key"):
                parts.append(str(k.key))
            elif hasattr(k, "idx"):
                parts.append(str(k.idx))
            else:
                parts.append(str(k))
        yield "/".join(parts), leaf
    return


def param_specs(params_shape, mesh: Mesh, *, fsdp: bool = False,
                expert_sharding: str = "ep"):
    """Pytree of PartitionSpec matching a params (shape-)pytree."""
    def leaf(path, x):
        return spec_for(path, tuple(x.shape), mesh, fsdp=fsdp,
                        expert_sharding=expert_sharding)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    specs = [leaf("/".join(_kp_str(kp)), l) for kp, l in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def _kp_str(kp):
    parts = []
    for k in kp:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return parts


def named(mesh: Mesh, spec_tree):
    """PartitionSpec pytree -> NamedSharding pytree."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def zero1_spec(param_spec: P, shape: Tuple[int, ...], mesh: Mesh,
               axis: str = "data") -> P:
    """ZeRO-1: add 'data' sharding on the first unsharded divisible dim."""
    size = _axis_size(mesh, axis)
    if size is None:
        return param_spec
    entries = list(param_spec) + [None] * (len(shape) - len(param_spec))
    used = {a for e in entries if e for a in ((e,) if isinstance(e, str) else e)}
    if axis in used:
        return param_spec
    for i, (e, dim) in enumerate(zip(entries, shape)):
        if e is None and dim % size == 0 and dim >= size:
            entries[i] = axis
            break
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def sketch_spec(mesh: Mesh, shape: Tuple[int, int, int], *,
                shards: int = 1, shard_axis: str = "model") -> P:
    """Sketch tensor (depth, width, dim).

    Replicated sketches (``shards == 1``, the pre-§17 default) keep the
    classic ZeRO-style placement: width→'data', dim→'model'.  A sketch
    whose spec declares ``shards > 1`` is a first-class sharded object
    (DESIGN.md §17): its width slabs LIVE on ``shard_axis`` — ``P(None,
    shard_axis)`` — and dim stays unsharded, because the routing
    collectives move whole (depth, k, dim) contribution rows per shard.
    When the mesh lacks the axis (or width doesn't divide) the sharded
    placement is impossible; callers that must not silently replicate
    (``opt_specs_for_state(strict=True)``) check that before calling."""
    _, w, d = shape
    if shards > 1:
        size = _axis_size(mesh, shard_axis)
        if size and w % size == 0:
            return P(None, shard_axis)
    axes = [None,
            "data" if (_axis_size(mesh, "data") or 0) and
            w % _axis_size(mesh, "data") == 0 else None,
            "model" if (_axis_size(mesh, "model") or 0) and
            d % _axis_size(mesh, "model") == 0 else None]
    while axes and axes[-1] is None:
        axes.pop()
    return P(*axes)


# Moment-tree tags an optimizer state may carry: the chain/legacy rules
# keep their EMAs under 'm'/'v'; the DP sparse-rows rule adds 'residual'
# (an error-feedback sketch in the v geometry).
_MOMENT_TAGS = ("m", "v", "residual")


def _looks_like_sketch(shape: Tuple[int, ...]) -> bool:
    """Cheap structural test: (depth ≤ 8, width, dim) rank-3 tensors."""
    return len(shape) == 3 and shape[0] <= 8


def opt_specs_for_state(state_shape, params_shape, mesh: Mesh, *,
                        fsdp: bool = False, expert_sharding: str = "ep",
                        store_tree=None, strict: bool = True):
    """Spec pytree for an optimizer-state pytree, resolving paths in the
    real ``chain``/``AuxStore`` state layout (DESIGN.md §12–13):

      * leading integer components (``chain`` tuple indices) are stripped,
        so ``0/m/<param path>`` and the legacy ``m/<param path>`` resolve
        identically;
      * dense moment leaves (same shape as their param) reuse the param
        spec + ZeRO-1 'data' sharding on the first free divisible dim;
      * sketch leaves — ``(depth, width, dim)`` — shard width over 'data'
        and dim over 'model'.  With a ``store_tree`` (``repro.core.stores
        .StoreTree``, e.g. ``Plan.store_tree()``) the classification is
        exact: a moment leaf is a sketch iff the tree resolves its param
        path to a sketch-backed store whose bound spec has this shape.
        Without one, the structural fallback (rank 3, depth ≤ 8, dim ==
        the param's trailing dim — or a bare single-table ``m``/``v``/
        ``residual`` state with no param path) applies;
      * ``Rank1Moment`` factors (trailing ``r``/``c`` vector leaves) and
        scalars (step counters) replicate.

    ``strict`` (default): a moment leaf that *looks* like a sketch but
    matches neither its param's shape nor a resolvable sketch spec raises
    instead of silently replicating — the failure mode that left sketch
    state unsharded when the state layout changed under the old rules.
    """
    param_shapes = {p: tuple(l.shape) for p, l in _iter_with_path(params_shape)}
    resolved_sketch_specs = (store_tree.sketch_state_specs(param_shapes)
                             if store_tree is not None else {})

    def leaf(path, x):
        if x is None or not hasattr(x, "shape") or x.ndim == 0:
            return P()
        shape = tuple(x.shape)
        parts = [p for p in path.split("/") if p]
        while parts and parts[0].isdigit():      # chain tuple indices
            parts.pop(0)
        if not parts:
            return P()
        tag, rest = parts[0], parts[1:]
        if tag not in _MOMENT_TAGS:
            return P()                           # step counters, scalars
        # Rank1Moment factors flatten with a trailing attribute key
        if rest and rest[-1].lstrip(".") in ("r", "c") and x.ndim == 1:
            return P()                           # rank-1 factors replicate
        # QuantState (int8 cells) flattens the same way: '.cells' IS the
        # (depth, width, dim) sketch tensor — classify it under its
        # param path like the f32 array it replaces; '.scales' is the
        # small per-(depth, block) sidecar and replicates (every width
        # shard needs its blocks' scales)
        if rest and rest[-1].lstrip(".") == "scales" and x.ndim == 2:
            return P()
        if rest and rest[-1].lstrip(".") == "cells" and x.ndim == 3:
            rest = rest[:-1]
        sub = "/".join(rest)
        pshape = param_shapes.get(sub)
        if pshape == shape:
            base = spec_for(sub, shape, mesh, fsdp=fsdp,
                            expert_sharding=expert_sharding)
            return zero1_spec(base, shape, mesh)
        if not sub and _looks_like_sketch(shape):
            return sketch_spec(mesh, shape)      # bare single-table state
        if store_tree is not None and sub:
            want = resolved_sketch_specs.get(
                ("v" if tag == "residual" else tag, sub))
            if want is not None and tuple(want.shape) == shape:
                if want.shards > 1:
                    size = _axis_size(mesh, "model")
                    if strict and (not size or shape[1] % size != 0):
                        raise ValueError(
                            f"optimizer-state leaf {path!r} resolves to a "
                            f"{want.shards}-shard sketch but the mesh has "
                            f"no 'model' axis dividing width {shape[1]} "
                            f"(axes {dict(zip(mesh.axis_names, mesh.devices.shape))}); "
                            f"refusing to silently replicate sharded "
                            f"sketch state")
                return sketch_spec(mesh, shape, shards=want.shards)
        elif _looks_like_sketch(shape) and pshape is not None \
                and len(pshape) == 2 and shape[2] == pshape[1]:
            return sketch_spec(mesh, shape)
        if strict and _looks_like_sketch(shape) and (
                not sub or pshape is None or len(pshape) == 2):
            raise ValueError(
                f"optimizer-state leaf {path!r} with sketch-like shape "
                f"{shape} matched no sharding rule (param shape "
                f"{pshape}); refusing to silently replicate sketch state "
                f"— pass the run's StoreTree or fix the rules")
        return P()

    flat, treedef = jax.tree_util.tree_flatten_with_path(
        state_shape, is_leaf=lambda x: x is None)
    specs = [leaf("/".join(_kp_str(kp)), l) for kp, l in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# Batch / activation helpers
# ---------------------------------------------------------------------------

def dp_axes(mesh: Mesh, batch: int) -> Tuple[str, ...]:
    """The data-parallel axis group ('pod','data' when present) that evenly
    divides ``batch`` — longest prefix wins, else fewer axes, else none."""
    cand = [a for a in ("pod", "data") if _axis_size(mesh, a)]
    while cand:
        size = 1
        for a in cand:
            size *= _axis_size(mesh, a)
        if batch % size == 0 and batch >= size:
            return tuple(cand)
        cand.pop(0)  # drop 'pod' first, keep 'data'
    return ()


def batch_spec(mesh: Mesh, shape: Tuple[int, ...], *,
               seq_axis: Optional[int] = None) -> P:
    """Shard dim0 over the DP axis group; optionally dim ``seq_axis`` over
    'model' (sequence parallelism for KV caches / long-context states)."""
    dp = dp_axes(mesh, shape[0])
    axes: list = [dp if dp else None] + [None] * (len(shape) - 1)
    if seq_axis is not None and _axis_size(mesh, "model") \
            and shape[seq_axis] % _axis_size(mesh, "model") == 0:
        axes[seq_axis] = "model"
    while axes and axes[-1] is None:
        axes.pop()
    return P(*axes)


_ACTIVE_MESH: list = []


class active_mesh:
    """Context manager: enters the jax mesh context AND registers the mesh
    so ``constraint`` calls inside traced code can adapt specs to it.  All
    tracing (train/serve step lowering) happens inside this context."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    def __enter__(self):
        _ACTIVE_MESH.append(self.mesh)
        self._ctx = self.mesh
        self._ctx.__enter__()
        return self.mesh

    def __exit__(self, *exc):
        self._ctx.__exit__(*exc)
        _ACTIVE_MESH.pop()
        return False


def current_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH[-1] if _ACTIVE_MESH else None


_MANUAL_DEPTH: list = []


class manual_collectives:
    """Context for tracing code INSIDE a ``shard_map`` body: mesh axes are
    manual there, so ``with_sharding_constraint`` is illegal —
    ``constraint`` becomes a no-op while this context is active (the DP
    train step wraps the model's loss in it; DESIGN.md §13)."""

    def __enter__(self):
        _MANUAL_DEPTH.append(True)
        return self

    def __exit__(self, *exc):
        _MANUAL_DEPTH.pop()
        return False


def in_manual_region() -> bool:
    """Whether tracing is inside a ``manual_collectives`` region."""
    return bool(_MANUAL_DEPTH)


def dp_sparse_wrap(local_fn, *, mesh: Optional[Mesh] = None,
                   dp_axis: str = "data"):
    """The one-table sparse DP calling convention, in one place: wrap
    ``local_fn(table, state, ids, rows) -> (table, state)`` in a
    ``shard_map`` over ``dp_axis`` with table/state replicated and the
    (ids, rows) batch sharded on dim 0.  ``mesh`` falls back to the
    active mesh at call/trace time (train sparse steps, serve adaptation,
    and the traffic benchmark's dense baseline all share this shape)."""

    def wrapped(table, state, ids, rows):
        use_mesh = mesh if mesh is not None else current_mesh()
        if use_mesh is None:
            raise ValueError(
                f"dp sparse steps over {dp_axis!r} need a mesh: pass "
                f"mesh= or trace inside shd.active_mesh(mesh)")
        dp = P(dp_axis)
        return jax.shard_map(
            local_fn, mesh=use_mesh, in_specs=(P(), P(), dp, dp),
            out_specs=(P(), P()), check_vma=False)(table, state, ids, rows)

    return wrapped


def sketch_state_specs(state, shard_axis: str = "model"):
    """Per-leaf PartitionSpec pytree for a sparse-rows optimizer state
    whose sketch moments are SHARDED (DESIGN.md §17): every rank-3
    ``(depth, width, dim)`` leaf — m / v / residual slabs share the
    geometry — slabs its width over ``shard_axis``; scalars (step) and
    everything else replicate.  Used both as shard_map in/out specs and
    (via ``named``) as the jit placement for the state."""
    def leaf(x):
        if hasattr(x, "ndim") and x.ndim == 3:
            return P(None, shard_axis)
        return P()
    return jax.tree_util.tree_map(leaf, state)


def sharded_sparse_wrap(local_fn, *, mesh: Optional[Mesh] = None,
                        dp_axis: Optional[str] = "data",
                        shard_axis: str = "model"):
    """The sharded-sketch sparse calling convention (DESIGN.md §17):
    wrap ``local_fn(table, state, ids, rows) -> (table, state)`` in a
    ``shard_map`` over the (dp × shard) mesh with

      * the table and non-sketch state replicated,
      * every rank-3 sketch leaf width-slabbed on ``shard_axis`` (the
        body sees its (depth, local_width, dim) slab),
      * the (ids, rows) batch sharded on ``dp_axis`` and replicated
        across ``shard_axis`` (``dp_axis=None``: fully replicated — the
        shard-only mesh).

    The body must be written in slab terms (``sharded_adam_rows``); its
    table/direction outputs are replicated by construction (psum- and
    all_gather-derived), which the static checker can't prove — hence
    ``check_vma=False``."""

    def wrapped(table, state, ids, rows):
        use_mesh = mesh if mesh is not None else current_mesh()
        if use_mesh is None:
            raise ValueError(
                f"sharded sparse steps over {shard_axis!r} need a mesh: "
                f"pass mesh= or trace inside shd.active_mesh(mesh)")
        dp = P(dp_axis) if dp_axis is not None else P()
        sspecs = sketch_state_specs(state, shard_axis)
        return jax.shard_map(
            local_fn, mesh=use_mesh, in_specs=(P(), sspecs, dp, dp),
            out_specs=(P(), sspecs), check_vma=False)(table, state, ids, rows)

    return wrapped


def constraint(x, spec: P):
    """with_sharding_constraint that is a no-op outside an ``active_mesh``
    context (or inside a ``manual_collectives`` region) and silently drops
    axes the mesh doesn't have / can't divide."""
    if _MANUAL_DEPTH:
        return x
    mesh = current_mesh()
    if mesh is None:
        return x
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    names = set(sizes)

    def fix_entry(entry, dim):
        if entry is None:
            return None
        group = entry if isinstance(entry, tuple) else (entry,)
        group = tuple(a for a in group if a in names)
        if not group:
            return None
        total = 1
        for a in group:
            total *= sizes[a]
        if dim % total != 0:
            return None
        return group if len(group) > 1 else group[0]

    entries = list(spec) + [None] * (x.ndim - len(spec))
    fixed = [fix_entry(e, d) for e, d in zip(entries, x.shape)]
    while fixed and fixed[-1] is None:
        fixed.pop()
    return jax.lax.with_sharding_constraint(x, P(*fixed))
