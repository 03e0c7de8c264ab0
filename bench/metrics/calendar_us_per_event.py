"""calendar_us_per_event (us/event): device time of the token calendar,
the operations under the program's ``vinelm/calendar`` scope (the
engines' token rates, the advance of their remaining work with the
calendar's counters, and the planner's delay row), in the traced calls:
the union of their intervals inside the epoch step's executions, clipped
to the traced window, averaged over the chips the cell uses, per
virtual-clock event of those calls.  The scope is nested in
``vinelm/clock`` and ``vinelm/dispatch``, whose readers count the same
operations where they ran.  None where the program names no such
scope."""
import re

import numpy as np

import scopes
from xplane import STEP_MODULE, union_ns

_CALENDAR = re.compile(r"vinelm/calendar(?:/|$)")


def _chip_ns(trace, device, smap, lo, hi):
    mods = sorted((s, e) for n, s, e in trace.modules.get(device, [])
                  if n.startswith(STEP_MODULE))
    ops = [(s, e) for text, s, e in trace.ops.get(device, [])
           if _CALENDAR.search(smap.get(text.split(" = ")[0].lstrip("%"),
                                        ""))]
    if not mods or not ops:
        return 0
    ms = np.array([m[0] for m in mods], np.int64)
    me = np.array([m[1] for m in mods], np.int64)
    s = np.array([o[0] for o in ops], np.int64)
    k = np.searchsorted(ms, s, side="right") - 1
    inside = (k >= 0) & (s < me[np.clip(k, 0, None)])
    return union_ns([(None, a, b) for (a, b), keep in zip(ops, inside)
                     if keep], lo, hi)


def read(ctx):
    w = ctx.trace.window()
    if w is None or not ctx.devices or not ctx.events:
        return None
    smap = scopes.scope_map()
    if not smap:
        return None
    per_chip = [_chip_ns(ctx.trace, d, smap, *w) for d in ctx.devices]
    if not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) / 1e3 / ctx.events
