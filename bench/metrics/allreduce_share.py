"""allreduce_share (%): device time in all-reduce operations (the one
plan merge per replan round of the sharded control plane) over the
traced window, averaged over the chips the cell uses."""
from xplane import union_ns


def read(ctx):
    w = ctx.trace.window()
    if w is None:
        return None
    lo, hi = w
    per_chip = [union_ns([o for o in ctx.trace.ops.get(d, [])
                          if "all-reduce" in o[0].lower()], lo, hi)
                for d in ctx.devices]
    if not per_chip or not any(per_chip):
        return None
    return 100.0 * sum(per_chip) / len(per_chip) / (hi - lo)
