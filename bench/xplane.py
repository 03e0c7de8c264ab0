"""Reading a profiler trace (``.xplane.pb``) into plain intervals.

`load` keeps what the per-layer readers need and nothing else: the
harness's own host spans (``bench.*`` names on the host's Python line),
and, per device, the executions of XLA modules (jitted programs) and of
the operations inside them.  All times are nanoseconds on the trace's one
clock.  The readers in ``bench/metrics/`` take a `Trace` and the run's
counts and return a number, or None where the trace holds nothing to
read.
"""
from __future__ import annotations

import dataclasses
import glob
import os

SPAN_PREFIX = "bench."
# the event engine's jitted epoch step, as its module is named on a device
STEP_MODULE = "jit_step"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    spans: list            # [(name, start_ns, end_ns)] harness host spans
    ops: dict              # device plane -> [(name, start_ns, end_ns)]
    modules: dict          # device plane -> [(name, start_ns, end_ns)]

    def calls(self) -> list:
        """The traced calls of the served entry, in time order."""
        return sorted((s, e) for n, s, e in self.spans if n == "bench.call")

    def window(self):
        """(start, end) of the traced window: first call start to last
        call end; None when no call was traced."""
        c = self.calls()
        return (c[0][0], c[-1][1]) if c else None


def find(trace_dir: str) -> str | None:
    """The newest ``.xplane.pb`` under ``trace_dir``, or None."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, ops, modules = [], {}, {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
        elif plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                out = ops if line.name == OPS_LINE else modules
                evs = out.setdefault(plane.name, [])
                for ev in line.events:
                    s = int(ev.start_ns)
                    evs.append((ev.name, s, s + int(ev.duration_ns)))
    return Trace(spans=spans, ops=ops, modules=modules)


def union_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of ``intervals`` clipped to [lo, hi]."""
    total, cur_s, cur_e = 0, None, None
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_intervals(trace: Trace, device: str):
    """A device's busy intervals: its operations, or its module
    executions where the trace has no operation line."""
    return trace.ops.get(device) or trace.modules.get(device) or []


def idle_gaps(intervals, lo: int, hi: int):
    """[(start, end)] of the gaps in ``intervals`` within [lo, hi]."""
    gaps, cur = [], lo
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        if s > cur:
            gaps.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        gaps.append((cur, hi))
    return [(s, e) for s, e in gaps if e > s]
