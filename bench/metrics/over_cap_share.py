"""over_cap_share (%): of the engine-seconds of virtual time in which an
engine held more than one sequence, the share the token calendar spent
above the engine's KV cap, ``over_cap_s / (batched_s + over_cap_s)``,
summed over the traced calls from their ``vinelm.drain`` spans
(``batched_s``: engine-seconds with 1 < occupancy <= the cap).  It says
whether the cell still works the calendar's queueing regime.  None where
the program counts neither."""
import scopes


def read(ctx):
    w = ctx.trace.window()
    if w is None:
        return None
    drains = [st for n, s, _, st in scopes.program_spans()
              if n == scopes.SPAN_PREFIX + "drain" and w[0] <= s <= w[1]]
    if not drains or not all("batched_s" in d and "over_cap_s" in d
                             for d in drains):
        return None
    over = sum(float(d["over_cap_s"]) for d in drains)
    busy = over + sum(float(d["batched_s"]) for d in drains)
    return 100.0 * over / busy if busy > 0 else None
