"""The program's own spans in a traced run: its ``train.*`` host spans and
the ``obs.*`` scope of each device op.

A TPU trace's op events carry only the HLO instruction's text.  The trace
also holds each module it ran as an HLO proto (the ``Hlo Proto`` stat of
the ``/host:metadata`` plane); its text goes through the program's own
``repro.obs.profiling.scope_map``, which maps every instruction to its
innermost ``obs.*`` scope.  A program without that function, or without
the spans, gives None here, never an error.

From these, beside what ``trace.Reduced`` holds:

* exclusive device time per op: its time less that of the ops nested in
  it on the same ``XLA Ops`` line (a ``while`` around kernel calls counts
  once), summed by scope;
* the host's own work per step: the ``train.*`` spans other than
  ``train.wait``;
* the offset of the device's clock from the host's: the trace places
  device events by the device's own offset from the start of the trace,
  and on a TPU v5e that start differs from the host's by a millisecond or
  two.  Each step bounds it: a module starts after its dispatch began and
  after the runtime enqueued its program (``DoEnqueueProgram``), and ends
  before its wait ended and before the runtime's read of the sync flag
  (``ReadSyncFlag``) returned.  The midpoint of the bounds is taken as the
  offset, their half-width as its error;
* for every traced step, on the host's clock so corrected: the dispatch's
  start to the module's start (launch) and the module's end to the wait's
  end (wake);
* idle gaps labeled by the ``train.*`` phase that covers most of each.

A metric reader finds the trace of its run where ``run.py`` writes it
(``.chipbench/trace/<cell>``) and uses it only if the device window matches
the reduction the harness made of it."""
from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re
import statistics
from typing import Dict, List, Optional, Tuple

from chipbench import trace

PROGRAM_PREFIX = "train."
WAIT = "train.wait"
DISPATCH = "train.dispatch"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
UNSCOPED = "unscoped"
MODULE_ID = re.compile(r"\((\d+)\)$")
ENQUEUE = "DoEnqueueProgram"        # the runtime hands a program to the chip
SYNC_READ = "ReadSyncFlag"          # the runtime reads a program's completion

Span = Tuple[str, float, float]           # (name, start, end) ns


# -- the HLO protos the trace holds ----------------------------------------

def _varint(b: bytes, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b: bytes):
    """(field number, value) of a protobuf message's wire format; a
    length-delimited value is its bytes."""
    i = 0
    while i < len(b):
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 1:
            v, i = b[i:i + 8], i + 8
        elif kind == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        elif kind == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {kind}")
        yield key >> 3, v


def _first(b: bytes, field: int):
    return next((v for f, v in _fields(b) if f == field), None)


def hlo_protos(path: str) -> Dict[str, bytes]:
    """``{program id: HloModuleProto bytes}`` from the trace's metadata
    plane (XSpace.planes = 1; XPlane: name 2, event_metadata 4,
    stat_metadata 5; a map entry: key 1, value 2; XEventMetadata.stats 5;
    XStat: metadata_id 1, bytes_value 6; HloProto.hlo_module 1)."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for field, plane in _fields(space):
        if field != 1 or _first(plane, 2) != METADATA_PLANE.encode():
            continue
        stat_ids = {_first(_first(e, 2), 1) for f, e in _fields(plane)
                    if f == 5 and _first(_first(e, 2), 2)
                    == HLO_PROTO_STAT.encode()}
        for f, entry in _fields(plane):
            if f != 4:
                continue
            meta = _first(entry, 2)
            for sf, stat in _fields(meta):
                if sf == 5 and _first(stat, 1) in stat_ids:
                    module = _first(_first(stat, 6) or b"", 1)
                    if module:
                        out[str(_first(entry, 1))] = module
    return out


def hlo_text(module_proto: bytes) -> str:
    """The module's text with its metadata (``XlaComputation.as_hlo_text``
    leaves the metadata out)."""
    from jax._src.lib import xla_client
    return xla_client._xla.HloModule.from_serialized_hlo_module_proto(
        module_proto).to_string()


def program_scope_map():
    """The program's ``scope_map``, or None where it has none."""
    try:
        from repro.obs import profiling
    except ImportError:
        return None
    return getattr(profiling, "scope_map", None)


# -- exclusive time ---------------------------------------------------------

def exclusive_ns(ops: List[trace.Op]) -> List[Tuple[trace.Op, float]]:
    """Each op of one line with its time less the time of the ops nested
    in it (an op that starts inside another is its child)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start,
                                                   -ops[i].end))
    excl = [o.end - o.start for o in ops]
    stack: List[int] = []
    for i in order:
        o = ops[i]
        while stack and ops[stack[-1]].end <= o.start:
            stack.pop()
        if stack:
            parent = ops[stack[-1]]
            excl[stack[-1]] -= min(o.end, parent.end) - o.start
        stack.append(i)
    return [(ops[i], excl[i]) for i in range(len(ops))]


def scope_ns(reduced: trace.Reduced, scope: str,
             scopes: Dict[str, str]) -> float:
    """Exclusive device time of the ops whose scope in ``scopes`` is
    ``scope`` (``unscoped`` takes the ops it does not name too), averaged
    over the devices."""
    if not reduced.devices:
        return 0.0
    tot = 0.0
    for ops in reduced.devices.values():
        for o, ns in exclusive_ns(ops):
            if scopes.get(o.name, UNSCOPED) == scope:
                tot += ns
    return tot / len(reduced.devices)


# -- one traced run ---------------------------------------------------------

@dataclasses.dataclass
class Spans:
    reduced: trace.Reduced
    program: List[Span]                    # train.* host spans, by start
    modules: Dict[str, List[Span]]         # per device: (id, start, end)
    hlo: Dict[str, bytes]                  # program id -> module proto
    runtime: List[Span] = dataclasses.field(default_factory=list)

    @functools.cached_property
    def scopes(self) -> Optional[Dict[str, str]]:
        """``{instruction: scope}`` over the modules that ran, or None
        where the program has no ``scope_map``."""
        fn = program_scope_map()
        if fn is None:
            return None
        ran = {m for mods in self.modules.values() for m, _, _ in mods}
        out: Dict[str, str] = {}
        for pid in sorted(ran & set(self.hlo)):
            out.update(fn(hlo_text(self.hlo[pid])))
        return out

    def missing_ops(self) -> List[str]:
        """Names of the ops the scope map does not hold."""
        sc = self.scopes or {}
        return sorted({o.name for ops in self.reduced.devices.values()
                       for o in ops if o.name not in sc})

    def scope_ns(self, scope: str) -> float:
        return scope_ns(self.reduced, scope, self.scopes or {})

    def device_by_scope(self) -> Dict[str, float]:
        """Exclusive device ns of every scope the ops fall in."""
        names = set((self.scopes or {}).values()) | {UNSCOPED}
        return {s: self.scope_ns(s) for s in sorted(names)}

    def host_ns(self) -> float:
        """The host's own work in the loop: ``train.*`` spans other than
        ``train.wait``."""
        return sum(e - s for n, s, e in self.program if n != WAIT)

    def _steps(self):
        """Per step, in order: (module start, module end) on the first
        device, and the step's dispatch and wait spans; None unless every
        one of them appears once per step."""
        if not self.modules:
            return None
        mods = sorted((a, b) for _, a, b in
                      self.modules[sorted(self.modules)[0]])
        disp = [(a, b) for n, a, b in self.program if n == DISPATCH]
        wait = [(a, b) for n, a, b in self.program if n == WAIT]
        if not mods or not len(mods) == len(disp) == len(wait):
            return None
        return list(zip(mods, disp, wait))

    def clock_offset(self) -> Optional[Tuple[float, float]]:
        """(lo, hi) ns: the bounds on the device clock less the host's that
        causality leaves, from the program's spans and, where each step
        has one, the runtime's enqueue and sync-flag read."""
        steps = self._steps()
        if steps is None:
            return None
        lo = max(m[1] - w[1] for m, _, w in steps)
        hi = min(m[0] - d[0] for m, d, _ in steps)
        enq = [a for n, a, _ in self.runtime if n == ENQUEUE]
        sync = [b for n, _, b in self.runtime if n == SYNC_READ]
        if len(enq) == len(steps):
            hi = min(hi, min(m[0] - e for (m, _, _), e in zip(steps, enq)))
        if len(sync) == len(steps):
            lo = max(lo, max(m[1] - r for (m, _, _), r in zip(steps, sync)))
        return lo, hi

    @functools.cached_property
    def offset(self) -> float:
        """The device clock less the host's, ns (0 where no step bounds
        it)."""
        b = self.clock_offset()
        return 0.0 if b is None else (b[0] + b[1]) / 2

    def label(self, gap: Tuple[float, float]) -> str:
        """The ``train.*`` span that covers most of ``gap`` (device clock),
        else what ``Reduced.label`` gives."""
        g = (gap[0] - self.offset, gap[1] - self.offset)
        best, cover = None, 0.0
        for name, s, e in self.program:
            c = min(e, g[1]) - max(s, g[0])
            if c > cover:
                best, cover = name, c
        return best or self.reduced.label(gap)

    def idle_gaps(self, top: int = 10) -> List[List]:
        gaps = []
        for d in self.reduced.devices:
            gaps += [(self.label(g), (g[1] - g[0]) / 1e9)
                     for g in self.reduced.gaps(d)]
        gaps.sort(key=lambda x: -x[1])
        return [[k, v] for k, v in gaps[:top]]

    def host_device(self) -> List[Tuple[float, float]]:
        """(launch, wake) ns of every step on the host's clock: its module's
        start less its dispatch's start, its wait's end less its module's
        end."""
        steps = self._steps()
        if steps is None:
            return []
        off = self.offset
        return [(m[0] - off - d[0], w[1] - (m[1] - off))
                for m, d, w in steps]

    def summary(self, steps: int) -> dict:
        """The per-step split of a traced window: device time by scope,
        the host's own work, launch and wake, and the labeled gaps."""
        hd = self.host_device()
        out = {"device_by_scope_ms": {k: v / 1e6 / steps for k, v in
                                      self.device_by_scope().items()},
               "busy_ms": self.reduced.busy_s * 1e3 / steps,
               "host_ms": self.host_ns() / 1e6 / steps,
               "missing_ops": self.missing_ops(),
               "idle_gaps": self.idle_gaps()}
        bounds = self.clock_offset()
        if bounds is not None:
            out["clock_offset_us"] = {"lo": bounds[0] / 1e3,
                                      "hi": bounds[1] / 1e3,
                                      "used": self.offset / 1e3}
        for i, key in enumerate(("launch_us", "wake_us")):
            vals = [x[i] / 1e3 for x in hd]
            if vals:
                out[key] = {"median": statistics.median(vals),
                            "min": min(vals), "max": max(vals),
                            "n": len(vals)}
        return out


def load(path: str, n_devices: Optional[int] = None) -> Spans:
    from jax.profiler import ProfileData
    reduced = trace.reduce_file(path, n_devices)
    program: List[Span] = []
    runtime: List[Span] = []
    modules: Dict[str, List[Span]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name in reduced.devices:
            for line in plane.lines:
                if line.name != MODULES_LINE:
                    continue
                for ev in line.events:
                    m = MODULE_ID.search(ev.name)
                    if m:
                        modules.setdefault(plane.name, []).append(
                            (m.group(1), ev.start_ns,
                             ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    span = (ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns)
                    if ev.name.startswith(PROGRAM_PREFIX):
                        program.append(span)
                    elif ev.name in (ENQUEUE, SYNC_READ):
                        runtime.append(span)
    program.sort(key=lambda x: x[1])
    runtime.sort(key=lambda x: x[1])
    return Spans(reduced=reduced, program=program, modules=modules,
                 hlo=hlo_protos(path), runtime=runtime)


def trace_file(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


@functools.lru_cache(maxsize=4)
def _load_cached(path: str, mtime: float, n_devices: int) -> Spans:
    return load(path, n_devices)


def of(ctx) -> Optional[Spans]:
    """The spans of the run a metric reader is reading: the trace ``run.py``
    keeps for ``ctx.cell`` while the run lasts, if its device window is the
    one the harness reduced; None otherwise."""
    from chipbench.run import OUT_DIR
    path = trace_file(str(OUT_DIR / "trace" / ctx.cell.name))
    if path is None:
        return None
    s = _load_cached(path, os.path.getmtime(path), ctx.cell.chips)
    if s.reduced.window != ctx.trace.window:
        return None
    return s
