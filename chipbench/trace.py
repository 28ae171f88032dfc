"""The reduction from a profiler trace to what the metrics read.

``reduce_dir`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote (via
``jax.profiler.ProfileData``, nothing else) into:

* per device: the operations that ran (``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane): the HLO instruction's name (a Pallas
  kernel's ``name``, a ``psum``) and opcode.  The events carry no
  ``named_scope`` path, so the program's ``obs.*`` scopes are not read;
* the host spans the benchmark opened (``bench.*`` TraceAnnotations).

From these: the traced window (the first op on any device to the last: the
host's start-up of the first traced step, before the device had work, is
not idle time of the loop), the busy time (union of op intervals)
averaged over the devices, per-op device time, the idle gaps and what the
host was doing in each (host and device clocks agree to a fraction of a
millisecond, so a label is the span that covers most of the gap)."""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."


@dataclasses.dataclass
class Op:
    name: str                  # the HLO instruction (``psum.46``)
    start: float               # ns
    end: float                 # ns
    opcode: str = ""           # its HLO opcode (``all-reduce``)


@dataclasses.dataclass
class Reduced:
    devices: Dict[str, List[Op]]
    host: List[Tuple[str, float, float]]       # (name, start, end) ns
    window: Tuple[float, float]                # ns

    @property
    def window_s(self) -> float:
        return max(0.0, (self.window[1] - self.window[0]) / 1e9)

    def busy_ns(self, dev: str) -> float:
        return sum(e - s for s, e in union(
            [(o.start, o.end) for o in self.devices[dev]], self.window))

    @property
    def busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(self.busy_ns(d) for d in self.devices) \
            / len(self.devices) / 1e9

    def op_ns(self, pred) -> float:
        """Device time of the ops ``pred`` selects, averaged over devices."""
        if not self.devices:
            return 0.0
        tot = sum(o.end - o.start for ops in self.devices.values()
                  for o in ops if pred(o))
        return tot / len(self.devices)

    def kernel_ns(self, kernel: str) -> float:
        """Device time of the Pallas kernel named ``kernel``."""
        return self.op_ns(lambda o: o.name.startswith(kernel))

    def gaps(self, dev: str) -> List[Tuple[float, float]]:
        busy = union([(o.start, o.end) for o in self.devices[dev]],
                     self.window)
        out, t = [], self.window[0]
        for s, e in busy:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if self.window[1] > t:
            out.append((t, self.window[1]))
        return out

    def label(self, gap: Tuple[float, float]) -> str:
        """The host span that covers most of ``gap`` (``host: waiting``
        when none of the benchmark's spans does)."""
        best, cover = "host: waiting on the device or untraced host work", 0.0
        for name, s, e in self.host:
            c = min(e, gap[1]) - max(s, gap[0])
            if c > cover:
                best, cover = name, c
        return best

    def breakdown(self, top: int = 10) -> dict:
        per: Dict[str, float] = {}
        for ops in self.devices.values():
            for o in ops:
                key = f"{o.name} ({o.opcode})" if o.opcode else o.name
                per[key] = per.get(key, 0.0) + (o.end - o.start)
        n = max(len(self.devices), 1)
        ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        for d in self.devices:
            gaps += [(self.label(g), (g[1] - g[0]) / 1e9)
                     for g in self.gaps(d)]
        gaps.sort(key=lambda x: -x[1])
        return {"device_ops": [[k, v / n / 1e9] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps[:top]]}


def union(intervals, window=None):
    """Sorted union of (start, end) intervals, clipped to ``window``."""
    out = []
    for s, e in sorted(intervals):
        if window is not None:
            s, e = max(s, window[0]), min(e, window[1])
            if e <= s:
                continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


OPCODE = re.compile(r"[\]})] ([a-z][a-z0-9_-]*)\(")


def op_name(text: str) -> str:
    """The HLO instruction's name from an op event's name, which on a TPU
    is the instruction's text (``%cs_adam_tiled.4 = (...) custom-call(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def op_code(text: str) -> str:
    """The HLO opcode from the instruction's text: the word before the
    operand list that follows the result shape (a ``psum`` of the program
    is an ``all-reduce``)."""
    m = OPCODE.search(text.split(" = ", 1)[-1])
    return m.group(1) if m else ""


def reduce_file(path: str, n_devices: Optional[int] = None) -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    host = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            if n_devices is not None and int(m.group(1)) >= n_devices:
                continue
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(Op(op_name(ev.name), ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  op_code(ev.name)))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    starts = [o.start for ops in devices.values() for o in ops]
    ends = [o.end for ops in devices.values() for o in ops]
    window = (min(starts), max(ends)) if starts else (0.0, 0.0)
    return Reduced(devices=devices, host=host, window=window)


def reduce_dir(trace_dir: str, n_devices: Optional[int] = None) -> Reduced:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(files[-1], n_devices)
