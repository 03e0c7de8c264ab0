"""The program's own scopes and spans in a traced run.

The program names each phase of its compiled epoch step with a named
scope under ``vinelm/`` (`SCOPES`; HLO metadata only) and each host phase
of a call with a ``vinelm.*`` profiler span.  A device trace names an
operation by its HLO text (``%while.404 = ...``), not by its scope, so
the join is a map from instruction name to scope path, which the program
builds on request (``repro.core.events_compiled.engine_scope_maps``):
`scope_map` fetches it after the window and writes it beside the trace.

A scope's self time is the union of the intervals of its operations and
of those of the scopes nested in it, less the union of the nested ones':
a ``while`` or ``conditional`` operation's interval encloses its body's
operations.  Operations count inside the epoch step's executions only,
clipped to the traced window; times are averaged over the chips the cell
uses, as the other readers do.

The spans are read from the trace file again (`xplane.load` keeps the
harness's own spans only): the ``vinelm.build`` span carries the trie's
shape, the ``vinelm.drain`` span the call's ``events``, ``sweeps`` and
``epochs``.

Where the program has no scope map or spans, as before they were added,
every reader built on this module returns None and raises nothing.
"""
from __future__ import annotations

import json
import os
import re

import numpy as np

from xplane import STEP_MODULE, find, union_ns

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")   # run.TRACE_DIR
SCOPES = ("clock", "admit", "dispatch", "plan")
SPAN_PREFIX = "vinelm."
_SCOPE = re.compile(r"vinelm/(" + "|".join(SCOPES) + r")(?:/|$)")

_memo: dict = {}


def _once(key, make):
    if key not in _memo:
        _memo[key] = make()
    return _memo[key]


def scope_map(trace_dir: str = TRACE_DIR) -> dict | None:
    """Instruction name -> scope path of the process's one engine
    program, also written to ``scope_map.json`` in ``trace_dir``; None
    where the program builds no such map or holds other than one."""
    def make():
        try:
            from repro.core.events_compiled import engine_scope_maps
        except ImportError:
            return None
        maps = engine_scope_maps()
        if len(maps) != 1:
            return None
        if os.path.isdir(trace_dir):
            with open(os.path.join(trace_dir, "scope_map.json"), "w") as f:
                json.dump(maps[0], f)
        return maps[0]
    return _once(("map", trace_dir), make)


def chain(op_name: str) -> tuple:
    """The ``vinelm/`` scopes an operation runs in, outermost first."""
    return tuple(_SCOPE.findall(op_name))


def _union(s: np.ndarray, e: np.ndarray) -> int:
    """Length of the union of the intervals [s, e)."""
    if not s.size:
        return 0
    o = np.argsort(s, kind="stable")
    s, e = s[o], e[o]
    # what the earlier intervals cover ends at their largest end
    reach = np.concatenate((s[:1], np.maximum.accumulate(e)[:-1]))
    return int(np.maximum(0, e - np.maximum(s, reach)).sum())


def _chip_times(trace, device: str, smap: dict, lo: int, hi: int) -> dict:
    mods = sorted((s, e) for n, s, e in trace.modules.get(device, [])
                  if n.startswith(STEP_MODULE))
    out = {"step": union_ns([(None, s, e) for s, e in mods], lo, hi),
           "scoped": 0, **{name: 0 for name in SCOPES}}
    ops = trace.ops.get(device, [])
    if not mods or not ops:
        return out
    codes: dict = {}

    def code(text):
        # (innermost scope, bit set of every scope the operation is in)
        if text not in codes:
            c = chain(smap.get(text.split(" = ")[0].lstrip("%"), ""))
            codes[text] = (SCOPES.index(c[-1]) if c else -1,
                           sum(1 << SCOPES.index(x) for x in set(c)))
        return codes[text]

    n = len(ops)
    s = np.fromiter((o[1] for o in ops), np.int64, n)
    e = np.fromiter((o[2] for o in ops), np.int64, n)
    ce = [code(o[0]) for o in ops]
    inner = np.fromiter((c[0] for c in ce), np.int64, n)
    bits = np.fromiter((c[1] for c in ce), np.int64, n)
    ms = np.array([m[0] for m in mods], np.int64)
    me = np.array([m[1] for m in mods], np.int64)
    k = np.searchsorted(ms, s, side="right") - 1
    keep = (k >= 0) & (s < me[np.clip(k, 0, None)])
    s, e = np.maximum(s, lo), np.minimum(e, hi)
    keep &= e > s
    s, e, inner, bits = s[keep], e[keep], inner[keep], bits[keep]
    out["scoped"] = _union(s[bits != 0], e[bits != 0])
    for i, name in enumerate(SCOPES):
        mine = (bits >> i) & 1 == 1
        nested = mine & (inner != i)
        out[name] = _union(s[mine], e[mine]) - _union(s[nested], e[nested])
    return out


def times(ctx) -> dict | None:
    """Nanoseconds per scope (self time), of all scoped operations
    (``scoped``) and of the step's executions (``step``) in the traced
    window, averaged over the cell's chips; None where no operation of
    the trace falls in a scope."""
    w = ctx.trace.window()
    if w is None or not ctx.devices or not any(
            ctx.trace.ops.get(d) for d in ctx.devices):
        return None

    def make():
        smap = scope_map()
        if not smap:
            return None
        per = [_chip_times(ctx.trace, d, smap, *w) for d in ctx.devices]
        out = {k: sum(p[k] for p in per) / len(per) for k in per[0]}
        return out if out["scoped"] > 0 else None
    return _once(("times", id(ctx.trace), tuple(ctx.devices)),
                 lambda: (ctx.trace, make()))[1]


def us_per_event(ctx, scope: str) -> float | None:
    """A scope's self time in microseconds per traced event."""
    t = times(ctx)
    if t is None or not ctx.events:
        return None
    return t[scope] / 1e3 / ctx.events


def program_spans(trace_dir: str = TRACE_DIR) -> list:
    """[(name, start_ns, end_ns, stats)] of the program's ``vinelm.*``
    host spans in the newest trace under ``trace_dir``."""
    def make():
        from jax.profiler import ProfileData

        path = find(trace_dir)
        if path is None:
            return []
        out = []
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        out.append((ev.name, s, s + int(ev.duration_ns),
                                    {k: v for k, v in ev.stats}))
        return sorted(out, key=lambda x: x[1])
    return _once(("spans", trace_dir, find(trace_dir)), make)


def traced_calls(ctx) -> dict | None:
    """The traced calls' trie shape (``nodes``, ``dmax``, ``models``,
    ``engines``, from their ``vinelm.build`` span) and summed ``sweeps``
    (from their ``vinelm.drain`` spans); None without such spans."""
    w = ctx.trace.window()
    if w is None:
        return None
    spans = [sp for sp in program_spans() if w[0] <= sp[1] <= w[1]]
    builds = [st for n, _, _, st in spans if n == SPAN_PREFIX + "build"]
    drains = [st for n, _, _, st in spans if n == SPAN_PREFIX + "drain"]
    shape = ("nodes", "dmax", "models", "engines")
    if not builds or not drains or not all(k in builds[0] for k in shape) \
            or not all("sweeps" in d for d in drains):
        return None
    out = {k: int(builds[0][k]) for k in shape}
    out["sweeps"] = sum(int(d["sweeps"]) for d in drains)
    return out


def device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind
