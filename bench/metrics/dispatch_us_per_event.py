"""dispatch_us_per_event (us/event): self time of the epoch step's
``vinelm/dispatch`` scope (a replan round outside its sweeps: the delay
row, the loop over needy lanes, dispatch, the overload trim, the merge
between chips) in the traced calls, averaged over the chips the cell
uses, per virtual-clock event of those calls."""
import scopes


def read(ctx):
    return scopes.us_per_event(ctx, "dispatch")
