"""clock_us_per_event (us/event): self time of the epoch step's
``vinelm/clock`` scope (the event loop and its float64 virtual clock: the
calendar advance, completions, deadline sheds, the next event's time, and
the loop's own sequencing between the phases' operations) in the traced
calls, averaged over the chips the cell uses, per virtual-clock event of
those calls."""
import scopes


def read(ctx):
    return scopes.us_per_event(ctx, "clock")
