"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Detailed rows are written to
reports/bench/*.json; each module is also runnable standalone for full
output (``python -m benchmarks.fig7_frontier`` etc.).
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import (admission, chaos, drift, fig7_frontier, fig8_mae,
                            fig9_policy, fig10_slo, fleet_throughput,
                            open_arrival, priority, roofline, table1_errors,
                            table2_profiling_cost, table3_overhead,
                            token_calendar, trace_replay)

    benches = [
        ("fig8_mae", fig8_mae.run),
        ("table1_errors", table1_errors.run),
        ("table2_profiling_cost", table2_profiling_cost.run),
        ("fig7_frontier", fig7_frontier.run),
        ("fig9_policy", fig9_policy.run),
        ("fig10_slo", fig10_slo.run),
        ("table3_overhead", table3_overhead.run),
        ("fleet_throughput", fleet_throughput.run),
        ("open_arrival", open_arrival.run),
        ("admission", admission.run),
        ("priority", priority.run),
        ("roofline", roofline.run),
        # the event-engine trajectory benchmarks (registered with
        # --tiny-equivalent sizes so the harness stays CI-runnable; the
        # full sweeps remain behind each module's standalone entrypoint)
        ("trace_replay", trace_replay.run),
        ("drift", lambda: drift.run(wf="nl2sql_2", n_requests=48,
                                    capacity=16, interval=1.0)),
        ("chaos", lambda: chaos.run(wf="nl2sql_2", n_requests=48,
                                    rate=3.0, capacity=10)),
        ("token_calendar", lambda: token_calendar.run(tiny=True)),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in benches:
        try:
            out = fn()
            print(f"{out['name']},{out['us_per_call']:.1f},{out['derived']}")
            sys.stdout.flush()
        except Exception as e:
            failures += 1
            print(f"{name},nan,FAILED:{type(e).__name__}:{e}")
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    from benchmarks.common import enable_compile_cache
    enable_compile_cache()
    main()
