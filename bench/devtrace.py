"""Reduction of a profiler trace to device busy time, top operations and
idle gaps, named by the harness span that was open on the host.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device
operations are the events of each ``/device:`` plane's ``XLA Ops`` line;
busy time is the union of their intervals, clipped to the traced window
(the host span ``bench.window``) and averaged over the devices.  Host spans
are the harness's ``bench.<span>#<n>`` annotations.
"""
from __future__ import annotations

import glob
import os
from bisect import bisect_right
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
# In a TPU trace the device's events sit up to about a millisecond early
# against the host's spans (a program shows starting before the span that
# dispatched it); a program belongs to a span when its midpoint falls
# within this much of it.
SLACK = 2e-3


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered(merged: Sequence[Interval], t0: float, t1: float) -> float:
    """Length of ``[t0, t1]`` covered by sorted disjoint ``merged``."""
    total = 0.0
    i = max(bisect_right([s for s, _ in merged], t0) - 1, 0)
    for s, e in merged[i:]:
        if s >= t1:
            break
        total += max(0.0, min(e, t1) - max(s, t0))
    return total


class Trace:
    """One traced run, in seconds on the trace's own clock."""

    def __init__(self, xplane_path: str):
        import jax

        data = jax.profiler.ProfileData.from_file(xplane_path)
        self.host: List[Tuple[str, float, float]] = []
        self.ops: Dict[str, List[Tuple[float, float, str]]] = {}
        self.programs: Dict[str, List[Tuple[float, float, str]]] = {}
        for plane in data.planes:
            if plane.name.startswith("/device:"):
                for line in plane.lines:
                    if line.name not in ("XLA Ops", "XLA Modules"):
                        continue
                    # an op is "%name = shape op(...)"; a program is
                    # "jit_name(fingerprint)"
                    evs = sorted((e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9,
                                  e.name.split(" = ", 1)[0].split("(", 1)[0])
                                 for e in line.events)
                    if evs:
                        (self.ops if line.name == "XLA Ops"
                         else self.programs)[plane.name] = evs
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("bench."):
                            self.host.append(
                                (e.name[len("bench."):], e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9))
        self.host.sort(key=lambda h: h[1])
        windows = [(s, e) for n, s, e in self.host if n == "window"]
        if windows:
            self.window: Interval = windows[0]
        elif self.ops:
            spans = [t for ops in self.ops.values() for t in ops]
            self.window = (min(s for s, _, _ in spans),
                           max(e for _, e, _ in spans))
        else:
            self.window = (0.0, 0.0)
        w0, w1 = self.window
        self.busy: Dict[str, List[Interval]] = {
            dev: union([(max(s, w0), min(e, w1)) for s, e, _ in ops
                        if e > w0 and s < w1])
            for dev, ops in self.ops.items()}

    @classmethod
    def from_dir(cls, log_dir: str) -> Optional["Trace"]:
        found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        return cls(sorted(found)[-1]) if found else None

    # ------------------------------------------------------------------
    @property
    def has_device(self) -> bool:
        return bool(self.ops)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Device busy seconds in the window, averaged over devices."""
        if not self.busy:
            return 0.0
        return sum(sum(e - s for s, e in b) for b in self.busy.values()) \
            / len(self.busy)

    def busy_in(self, t0: float, t1: float) -> float:
        """Device busy seconds inside ``[t0, t1]``, averaged over devices."""
        if not self.busy:
            return 0.0
        return sum(covered(b, t0, t1) for b in self.busy.values()) \
            / len(self.busy)

    def program_time_in(self, t0: float, t1: float,
                        slack: float = SLACK) -> float:
        """Device seconds of the programs whose midpoint falls inside the
        host interval ``[t0, t1]`` (widened by ``slack`` for the offset
        between the host's and the device's clocks in the trace),
        averaged over devices."""
        if not self.programs:
            return 0.0
        total = 0.0
        for progs in self.programs.values():
            for s, e, _ in progs:
                if t0 - slack <= (s + e) / 2 <= t1 + slack:
                    total += e - s
        return total / len(self.programs)

    def spans(self, name: str) -> Dict[int, Interval]:
        """Host intervals of the harness span ``name``, by call number."""
        out: Dict[int, Interval] = {}
        for n, s, e in self.host:
            base, _, num = n.partition("#")
            if base == name and num.isdigit():
                out[int(num)] = (s, e)
        return out

    def top_ops(self, k: int = 10) -> List[List[object]]:
        """The ``k`` device programs (jitted functions, ``jit_<name>``)
        with the most device time in the window, averaged over devices.
        Programs, not their ops: a loop op's time would count its body's
        ops twice."""
        w0, w1 = self.window
        total: Dict[str, float] = {}
        for progs in self.programs.values():
            for s, e, name in progs:
                t = min(e, w1) - max(s, w0)
                if t > 0:
                    total[name] = (total.get(name, 0.0)
                                   + t / len(self.programs))
        return [[n, t] for n, t in
                sorted(total.items(), key=lambda x: -x[1])[:k]]

    def host_segments(self) -> List[Tuple[float, float, str]]:
        """The window cut at every harness span boundary, each piece named
        by the innermost span open over it ("outside spans" where none).
        The harness's spans come from one thread, so they nest."""
        w0, w1 = self.window
        spans = sorted(((s, e, n.split("#", 1)[0]) for n, s, e in self.host
                        if n != "window"), key=lambda x: (x[0], -x[1]))
        out: List[Tuple[float, float, str]] = []
        stack: List[Tuple[float, str]] = []
        t = w0

        def upto(end: float, name: str) -> None:
            nonlocal t
            if end > t:
                out.append((t, min(end, w1), name))
                t = end

        for s, e, n in spans:
            while stack and stack[-1][0] <= s:
                end, name = stack.pop()
                upto(end, name)
            upto(s, stack[-1][1] if stack else "outside spans")
            stack.append((e, n))
        while stack:
            end, name = stack.pop()
            upto(end, name)
        upto(w1, "outside spans")
        return [seg for seg in out if seg[1] > seg[0]]

    def idle_gaps(self, k: int = 10) -> List[List[object]]:
        """Device idle seconds in the window by what the host was doing:
        each idle interval is split along :meth:`host_segments`; the ``k``
        largest totals."""
        if not self.busy:
            return []
        w0, w1 = self.window
        dev = sorted(self.busy)[0]
        edges = [w0] + [t for iv in self.busy[dev] for t in iv] + [w1]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        total: Dict[str, float] = {}
        segs = self.host_segments()
        j = 0
        for a, b in idle:
            while j < len(segs) and segs[j][1] <= a:
                j += 1
            i = j
            while i < len(segs) and segs[i][0] < b:
                s, e, name = segs[i]
                t = min(b, e) - max(a, s)
                if t > 0:
                    total[name] = total.get(name, 0.0) + t
                i += 1
        return [[n, t] for n, t in
                sorted(total.items(), key=lambda x: -x[1])[:k]]
