"""admit_us_per_event (us/event): self time of the epoch step's
``vinelm/admit`` scope (arrivals, queue rejections, preemption and
admission, the replan cycle's loop test) in the traced calls, averaged
over the chips the cell uses, per virtual-clock event of those calls."""
import scopes


def read(ctx):
    return scopes.us_per_event(ctx, "admit")
