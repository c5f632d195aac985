"""Profiler capture and its reduction to numbers.

The JAX profiler writes one ``.xplane.pb`` per capture. In it, each chip
is a plane named ``/device:TPU:<n>`` whose ``XLA Ops`` line holds one
event per device operation, named by its HLO text (``%name.3 = ...``; a
Pallas kernel's operation carries the kernel's own name). The host is
the plane ``/host:CPU``; the benchmark's spans (``bench.*``, from
``jax.profiler.TraceAnnotation``) lie on its ``python`` line. Event
times are nanoseconds from the start of the capture on one clock for
host and device.

From that:

* busy time: the union of a chip's operation intervals, averaged over
  the chips used;
* the window: the ``bench.window`` spans, one per unit of work (the
  comparison of each unit with the reference runs between them, in a
  ``bench.verify`` span, and is not part of the window);
* device time by operation name, within given host spans;
* ``breakdown``: the ten operations that took most device time, and the
  idle gaps on the device grouped by what the host was doing in them.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
ATTRIBUTED_GAPS = 200       # longest gaps matched to a host event
_OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\s*=.*)?$",
                      re.DOTALL)


def op_name(event_name: str) -> str:
    """'fused_verify_decrypt' for '%fused_verify_decrypt.1 = (s32[..."""
    head = event_name.split(" = ", 1)[0].strip()
    m = _OP_NAME.match(head)
    return m.group(1) if m else head


def union(intervals) -> list:
    """Merged, sorted [start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap(merged: list, lo: float, hi: float) -> float:
    """Length of `merged` (sorted, disjoint) inside [lo, hi)."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


@dataclass
class Trace:
    """A reduced capture. Times are nanoseconds on the capture's clock."""
    ops: dict                       # chip -> [(op name, start, end)]
    spans: list                     # [(name, start, end)] bench.* spans
    host: list = field(default_factory=list)   # [(name, start, end)]

    def __post_init__(self):
        self._host_arrays = None
        self.busy = {c: union((s, e) for _, s, e in evs)
                     for c, evs in self.ops.items()}

    @property
    def windows(self) -> list:
        return self.spans_named(WINDOW_SPAN)

    def spans_named(self, name: str) -> list:
        return [(s, e) for n, s, e in self.spans if n == name]

    def busy_ns(self, within: list | None = None) -> float | None:
        """Device busy time, averaged over chips, inside `within` spans
        (default: the window). None when no chip is in the capture."""
        if not self.busy:
            return None
        within = within if within is not None else self.windows
        return sum(sum(overlap(b, s, e) for s, e in within)
                   for b in self.busy.values()) / len(self.busy)

    def busy_s(self) -> float | None:
        b = self.busy_ns()
        return None if b is None or not self.windows else b / 1e9

    def window_s(self) -> float | None:
        w = self.windows
        return sum(e - s for s, e in w) / 1e9 if w else None

    def op_ns(self, names, within: list | None = None) -> float | None:
        """Device time of the operations named `names` inside `within`
        spans (default: the window), summed over chips; None when no such
        operation ran."""
        within = within if within is not None else self.windows
        names = set(names)
        total, found = 0.0, False
        for evs in self.ops.values():
            for n, s, e in evs:
                if n in names:
                    found = True
                    total += sum(max(0.0, min(e, hi) - max(s, lo))
                                 for lo, hi in within)
        return total if found else None

    def breakdown(self) -> dict:
        windows = self.windows
        by_op: dict = {}
        for evs in self.ops.values():
            for n, s, e in evs:
                d = sum(max(0.0, min(e, hi) - max(s, lo))
                        for lo, hi in windows)
                if d:
                    by_op[n] = by_op.get(n, 0.0) + d
        nchips = max(1, len(self.ops))
        device_ops = sorted(([n, t / nchips / 1e9] for n, t in by_op.items()),
                            key=lambda kv: -kv[1])[:10]
        gaps = []
        for busy in self.busy.values():
            for lo, hi in windows:
                edges = [lo] + [x for iv in busy for x in iv] + [hi]
                for s, e in zip(edges[0::2], edges[1::2]):
                    s, e = max(s, lo), min(e, hi)
                    if e > s:
                        gaps.append((s, e))
        gaps.sort(key=lambda g: g[0] - g[1])
        named: dict = {}
        for k, (s, e) in enumerate(gaps):
            name = self.host_doing(s, e, events=k < ATTRIBUTED_GAPS)
            named[name] = named.get(name, 0.0) + (e - s) / nchips
        idle_gaps = sorted(([n, t / 1e9] for n, t in named.items()),
                           key=lambda kv: -kv[1])[:10]
        return {"device_ops": device_ops, "idle_gaps": idle_gaps}

    def host_doing(self, lo: float, hi: float, events: bool = True) -> str:
        """What the host was doing in the gap [lo, hi): the innermost
        bench span over its middle and, with `events`, the innermost of
        the host events that overlap the gap most."""
        mid = (lo + hi) / 2
        inner = [(e - s, n) for n, s, e in self.spans
                 if s <= mid < e and n != WINDOW_SPAN]
        span = min(inner)[1] if inner else WINDOW_SPAN
        if not events or not self.host:
            return span
        import numpy as np
        if self._host_arrays is None:
            self._host_arrays = (np.array([s for _, s, _ in self.host], float),
                                 np.array([e for _, _, e in self.host], float))
        starts, ends = self._host_arrays
        ov = np.minimum(ends, hi) - np.maximum(starts, lo)
        best = ov.max()
        if best <= 0:
            return span
        cand = np.flatnonzero(ov == best)
        k = cand[np.argmin((ends - starts)[cand])]
        return f"{span}: {self.host[k][0]}"


def from_xspace(pd) -> Trace:
    """Reduce a ``jax.profiler.ProfileData``."""
    ops: dict = {}
    spans: list = []
    host: list = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((op_name(e.name), e.start_ns,
                                e.start_ns + e.duration_ns)
                               for e in line.events)
            ops[int(m.group(1))] = evs
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    iv = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(iv)
                    elif e.duration_ns > 0:
                        host.append(iv)
    return Trace(ops, spans, host)


class Capture:
    """Starts and stops the profiler over the window, and reduces it."""

    def __init__(self, log_dir: Path):
        self.log_dir = Path(log_dir)

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # no per-call Python events
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def reduce(self, chips: int) -> Trace | None:
        import jax
        files = sorted(self.log_dir.glob("plugins/profile/*/*.xplane.pb"))
        if not files:
            return None
        tr = from_xspace(jax.profiler.ProfileData.from_file(str(files[-1])))
        tr.ops = {c: evs for c, evs in tr.ops.items() if c < chips}
        tr.busy = {c: b for c, b in tr.busy.items() if c < chips}
        return tr


def idle_pct(trace: Trace | None) -> float | None:
    """100 * (1 - device busy / window), or None with no chip traced."""
    if trace is None:
        return None
    busy, window = trace.busy_s(), trace.window_s()
    if busy is None or not window:
        return None
    return 100.0 * (1.0 - busy / window)
