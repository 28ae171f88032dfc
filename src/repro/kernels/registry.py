"""Shared kernel-backend registry: (store kind, op) → {backend: fn}.

PR 1 introduced interchangeable implementations ("backends") for the
sparse-rows CS-Adam step, keyed by name in ``kernels/__init__.py``.  The
fused-store refactor (DESIGN.md §14) adds a second kernelized op — the
dense-path ``update_read`` of the ``AuxStore`` protocol — so the flat
name → fn table becomes a two-level registry dispatching on

    kind    which store owns the op: 'sketch' (signed Count-Sketch),
            'countmin' (unsigned Count-Min), or 'pair' (ops spanning an
            (m, v) store pair, e.g. the fused sparse-rows Adam step);
    op      the protocol operation ('adam_rows' | 'update_read');
    backend the implementation name ('ref' | 'xla' | 'stream' | 'tiled'
            | 'interpret' | ...), with None/'auto' resolved per platform
            (Pallas 'tiled' on TPU, vectorized 'xla' elsewhere).

Not every (kind, op) offers every backend — 'stream' (one item per grid
step) exists only for the sparse-rows pair op, where exact per-item
ordering matters; the dense ``update_read`` is defined batch-wise and
registers ref | xla | tiled | interpret.  ``backends(kind, op)``
enumerates what is actually available; new implementations (e.g. a GPU
port) attach via ``register``.

``kernels/__init__.py`` keeps the PR-1 flat API (``register_backend`` /
``backends()`` / ``resolve_backend`` / ``adam_rows``) as thin wrappers
over the ('pair', 'adam_rows') row of this registry.

A backend may register a ``refusal(spec) -> Optional[str]``: the reason
it cannot run a sketch (the Pallas kernels need whole 128-lane rows and
f32/bf16 cells; see ``row_groups.tiled_refusal``).  ``resolve`` with the
op's specs then sends None/'auto' to 'xla' for such a sketch — by name,
so what runs is what ``resolve`` returns — and raises for an explicit
name.  ``recording()`` collects every spec-bearing resolution made while
it is open (the launcher reports what its compiled step runs).
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax

# (kind, op) -> {backend name: fn}, insertion-ordered per row.
_REGISTRY: Dict[Tuple[str, str], Dict[str, Callable]] = {}
# (kind, op, backend) -> refusal(spec) -> reason | None
_REFUSALS: Dict[Tuple[str, str, str], Callable] = {}
_RECORDERS: List[list] = []

# Per-platform default picked by resolve(..., None/'auto'): the Pallas
# tiled pipeline on TPU, the vectorized jnp path everywhere else.
_AUTO = {"tpu": "tiled"}
_AUTO_FALLBACK = "xla"


def register(kind: str, op: str, backend: str, fn: Callable,
             refusal: Optional[Callable] = None) -> None:
    """Register (or override) one implementation of ``op`` for ``kind``;
    ``refusal(spec)`` names why it cannot run a sketch (None: it can)."""
    _REGISTRY.setdefault((kind, op), {})[backend] = fn
    _REFUSALS.pop((kind, op, backend), None)
    if refusal is not None:
        _REFUSALS[(kind, op, backend)] = refusal


def ops() -> Tuple[Tuple[str, str], ...]:
    """Every registered (kind, op) row."""
    return tuple(_REGISTRY)


def backends(kind: str, op: str) -> Tuple[str, ...]:
    """Backend names registered for (kind, op), registration order."""
    row = _REGISTRY.get((kind, op))
    if row is None:
        raise KeyError(f"no kernels registered for kind={kind!r} op={op!r}; "
                       f"rows: {ops()}")
    return tuple(row)


def refusal(kind: str, op: str, backend: str, specs=()) -> Optional[str]:
    """Why ``backend`` cannot run (kind, op) on these sketch specs (None
    when it can, or when it registered no refusal)."""
    fn = _REFUSALS.get((kind, op, backend))
    for spec in specs:
        why = None if fn is None or spec is None else fn(spec)
        if why is not None:
            return why
    return None


def resolve(kind: str, op: str, backend: Optional[str] = None,
            specs=()) -> str:
    """Map None/'auto' to this host's best backend for (kind, op) — 'xla'
    for a sketch the best one refuses; validate explicit names against
    the registered row and against ``specs``."""
    names = backends(kind, op)
    if backend is None or backend == "auto":
        name = _AUTO.get(jax.default_backend(), _AUTO_FALLBACK)
        if name not in names:
            name = names[0]
        if refusal(kind, op, name, specs) is not None:
            name = _AUTO_FALLBACK
    elif backend not in names:
        raise KeyError(f"unknown backend {backend!r} for kind={kind!r} "
                       f"op={op!r}; registered: {names}")
    else:
        name = backend
        why = refusal(kind, op, name, specs)
        if why is not None:
            raise ValueError(
                f"backend {name!r} cannot run {kind}/{op} here: {why} — "
                f"pass 'auto' to let the registry pick, or 'xla'")
    if specs:
        for rec in _RECORDERS:
            rec.append((kind, op, name, tuple(
                _describe(s) for s in specs if s is not None)))
    return name


def _describe(spec) -> str:
    return (f"{spec.depth}x{spec.width}x{spec.dim} "
            f"{getattr(spec.dtype, 'name', spec.dtype)}")


@contextlib.contextmanager
def recording() -> Iterator[list]:
    """Collect ``(kind, op, backend, spec descriptions)`` for every
    spec-bearing ``resolve`` made inside the block (typically while a
    step traces)."""
    rec: list = []
    _RECORDERS.append(rec)
    try:
        yield rec
    finally:
        _RECORDERS.remove(rec)


def lookup(kind: str, op: str, backend: Optional[str] = None) -> Callable:
    """The implementation executing (kind, op) on ``backend`` (None/'auto'
    = per-host best)."""
    return _REGISTRY[(kind, op)][resolve(kind, op, backend)]
