"""idle_share (%): the share of the traced window in which no operation
ran on the device, averaged over the chips the cell uses: one minus the
union of the device's operation intervals over the window's length."""
from xplane import busy_intervals, union_ns


def read(ctx):
    w = ctx.trace.window()
    if w is None:
        return None
    lo, hi = w
    busy = [union_ns(busy_intervals(ctx.trace, d), lo, hi)
            for d in ctx.devices]
    if not busy or not any(busy):
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
