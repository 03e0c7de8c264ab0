"""host_ms_per_call (ms): the host's own share of a call of the served
entry: from the start of the harness's ``bench.call`` span to the first
execution of the epoch step on the device, plus from the last one's end
to the span's end (input tabulation, trie upload, state set-up, result
drain), averaged over the traced calls."""
from xplane import STEP_MODULE


def read(ctx):
    mods = sorted((s, e) for d in ctx.devices
                  for n, s, e in ctx.trace.modules.get(d, [])
                  if n.startswith(STEP_MODULE))
    outs = []
    for cs, ce in ctx.trace.calls():
        inside = [(s, e) for s, e in mods if s >= cs and e <= ce]
        if inside:
            first = min(s for s, _ in inside)
            last = max(e for _, e in inside)
            outs.append((first - cs) + (ce - last))
    if not outs:
        return None
    return sum(outs) / len(outs) / 1e6
