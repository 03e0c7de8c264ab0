"""plan_us_per_event (us/event): device time of the ``vinelm/plan`` scope
(the width-1 replan sweeps of ``kernels/ops.trie_plan``, whatever
variant) in the traced calls, averaged over the chips the cell uses, per
virtual-clock event of those calls."""
import scopes


def read(ctx):
    return scopes.us_per_event(ctx, "plan")
