"""step_us_per_event (us/event): device time of the compiled epoch step
(the jitted ``step`` of the event engine) in the traced calls, averaged
over the chips the cell uses, per virtual-clock event of those calls."""
from xplane import STEP_MODULE, union_ns


def read(ctx):
    w = ctx.trace.window()
    if w is None or not ctx.events:
        return None
    lo, hi = w
    per_chip = [union_ns([m for m in ctx.trace.modules.get(d, [])
                          if m[0].startswith(STEP_MODULE)], lo, hi)
                for d in ctx.devices]
    if not per_chip or not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) / 1e3 / ctx.events
