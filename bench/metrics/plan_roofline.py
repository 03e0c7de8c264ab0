"""plan_roofline (%): the replan sweeps' share of the chip's roofline.
The traced calls' sweeps (counted by the program, from their
``vinelm.drain`` spans) times one width-1 sweep's operations and bytes
over the trie the ``vinelm.build`` span describes (`plan_work.py`), over
the device time of the ``vinelm/plan`` scope summed over the chips the
cell uses, at the published peaks of the chip's ``device_kind``."""
import importlib.util
import os

import scopes

_spec = importlib.util.spec_from_file_location(
    "bench_plan_work", os.path.join(os.path.dirname(__file__),
                                    "plan_work.py"))
plan_work = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(plan_work)


def read(ctx):
    t = scopes.times(ctx)
    if t is None or not t["plan"]:
        return None
    calls = scopes.traced_calls(ctx)
    if calls is None or not calls["sweeps"]:
        return None
    ops, nbytes = plan_work.sweep_work(calls["nodes"], calls["dmax"],
                                       calls["models"], calls["engines"])
    seconds = t["plan"] * len(ctx.devices) / 1e9
    share, _ = plan_work.roofline_share(calls["sweeps"] * ops,
                                        calls["sweeps"] * nbytes, seconds,
                                        scopes.device_kind())
    return share
