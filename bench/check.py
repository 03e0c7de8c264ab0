"""The comparison that decides `correct`.

Each call of the window returns a streamed summary.  After the window,
the plain reference (`reference.Reference.simulate`) replays every call's
requests, and three numbers are compared per call, each held to its
limit at its worst over the calls:

- ``count_gap``: the largest difference, in requests or events, between
  the program's and the reference's counts (events, replans, served,
  succeeded, rejected, shed, deadline misses, requests);
- ``latency_gap``: the difference in the summed latency of the served
  requests, relative to the reference's;
- ``cost_gap``: the same for their summed dollar cost.

The limits are set, as `PERF.md` records, between the readings of the
program on the chip (float64 clock emulated in float32 pairs, float32
planner) and those of the controls: the reference planning in bfloat16,
and the reference keeping its clock in float32.
"""
from __future__ import annotations

COUNTS = ("n_requests", "events", "replans", "served", "succeeded",
          "rejected", "shed", "slo_violations")
LIMITS = {
    "count_gap": 10.0,
    "latency_gap": 1e-5,
    "cost_gap": 1e-5,
}


def program_summary(s: dict) -> dict:
    """The reference's fields from a program's streamed summary."""
    out = {k: int(s[k]) for k in COUNTS}
    out["latency_sum"] = float(s["latency"]["mean"]) * float(
        s["latency"]["count"])
    out["cost_sum"] = float(s["cost"]["mean"]) * float(s["cost"]["count"])
    return out


def _rel(a: float, ref: float) -> float:
    if ref:
        return abs(a - ref) / abs(ref)
    return 0.0 if a == 0 else float("inf")


def gaps(prog: dict, ref: dict) -> dict:
    """The compared numbers of one call."""
    return {
        "count_gap": float(max(abs(prog[k] - ref[k]) for k in COUNTS)),
        "latency_gap": _rel(prog["latency_sum"], ref["latency_sum"]),
        "cost_gap": _rel(prog["cost_sum"], ref["cost_sum"]),
    }


def worst(per_call: list) -> dict:
    """Each number at its worst over the calls (inf with no call)."""
    return {k: max((g[k] for g in per_call), default=float("inf"))
            for k in LIMITS}


def passes(w: dict) -> bool:
    return all(w[k] <= LIMITS[k] for k in LIMITS)
