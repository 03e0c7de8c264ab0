"""Token-level engine calendar: curve fidelity + p99 estimation error.

Validates the ISSUE-10 token work model (`repro.serving.loadsim`
`EngineTokenModel` / `TokenWorkModel`) end to end.  Two gates, both hard
failures:

(a) **curve fidelity** — for each roofline-derived engine model, inject
    ``b`` equal decode jobs into `FleetEngineSim` and require the
    simulated engine throughput ``b x d / T`` to match the analytic
    continuous-batching curve `EngineTokenModel.decode_tok_s(b)` within
    10% across the swept batch sizes, including beyond the KV cap where
    sequences timeshare the saturated batch;

(b) **estimation error** — on the open-arrival sweep the serving
    simulation's p99-latency estimate under ``work_model="tokens"`` must
    be STRICTLY more accurate than under the scalar processor-sharing
    model.  Ground truth is an independent token-physics replay (below,
    separate code from the engine calendar) of each lane's own realized
    schedule: same arrivals, same executed stage sequences, FIFO slot
    admission, continuous-batching drain.  The scalar knee is free below
    its concurrency and timeshares above it, so it misses the sub-cap
    batching stretch ``step(b)/step(1)`` entirely — that gap is what
    this gate measures.

The sweep additionally replays every rate through the compiled
epoch-batched engine with a bitwise consistency check (outcomes, model
sequences, and realized latencies must be identical to the host loop)
and pins ZERO planner/engine re-traces after warmup via
`fleet_planner_cache_size` / `compiled_engine_cache_size`.

    PYTHONPATH=src python -m benchmarks.token_calendar [--tiny]
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks.common import (
    enable_compile_cache,
    exact_ann,
    save_report,
    workload,
)
from repro.configs import get_config
from repro.core.controller import Objective
from repro.core.controller_jax import fleet_planner_cache_size
from repro.core.events import run_events
from repro.core.events_compiled import compiled_engine_cache_size
from repro.core.runtime import make_workload_executor
from repro.core.workload import poisson_arrivals
from repro.serving.loadsim import (EngineLoadModel, EngineTokenModel,
                                   FleetEngineSim, FleetLoadModel,
                                   TokenWorkModel)

# arch presets behind each serving engine (cycled over the preset's
# engine list) — distinct rooflines so the curves differ per engine
ENGINE_ARCHS = ("yi-9b", "qwen2-72b", "mistral-nemo-12b", "minicpm3-4b")
CURVE_ARCHS_FULL = ("yi-9b", "qwen2-72b", "granite-moe-1b-a400m",
                    "minicpm3-4b")
CURVE_ARCHS_TINY = ("yi-9b", "minicpm3-4b")
# offered-load multipliers relative to the nominal fleet service rate
LOAD_FACTORS_FULL = (0.5, 1.0, 2.0)
LOAD_FACTORS_TINY = (0.75, 1.5)
CURVE_TOL = 0.10
DECODE_PER_JOB = 64.0  # decode tokens per injected curve-check job


def _curve_rows(archs) -> list[dict]:
    """Gate (a): simulated batch throughput vs the analytic curve."""
    rows = []
    for arch in archs:
        m = EngineTokenModel.from_roofline(
            arch, get_config(arch), context_len=2048,
            kv_budget_bytes=4 << 30)
        cap = int(m.kv_capacity)
        batches = sorted({1, 2, max(cap // 2, 1), cap, 2 * cap})
        for b in batches:
            sim = FleetEngineSim([arch], capacity=b,
                                 token_models={arch: m})
            work = DECODE_PER_JOB * m.decode_step_s(1.0)
            for slot in range(b):
                sim.start(slot, 0, work, 0.0)
            t_done = sim.next_completion()
            got = b * DECODE_PER_JOB / t_done
            want = m.decode_tok_s(b)
            err = abs(got - want) / want
            if err > CURVE_TOL:
                raise RuntimeError(
                    f"token calendar off the roofline curve: {arch} at "
                    f"batch={b} simulated {got:.1f} tok/s vs analytic "
                    f"{want:.1f} tok/s ({err * 100:.1f}% > "
                    f"{CURVE_TOL * 100:.0f}%)")
            rows.append({
                "kind": "curve", "arch": arch, "batch": b,
                "kv_capacity": cap,
                "sim_tok_s": round(got, 2),
                "analytic_tok_s": round(want, 2),
                "rel_err": round(err, 6),
            })
    return rows


def _token_replay(arrivals, seqs, params, capacity: int) -> np.ndarray:
    """Independent token-physics ground truth: replay realized stage
    sequences under continuous-batching drain with FIFO slot admission.

    ``seqs[i]`` is request i's realized schedule ``[(engine_idx,
    work_s), ...]`` (work in batch-1 seconds); ``params`` is the
    per-engine ``(t_weights, t_kv, t_flop, kv_cap, step1)`` tuple-of-
    arrays.  Deliberately shares NO code with `FleetEngineSim` — this is
    the oracle the estimation-error gate judges both lanes against.
    Returns per-request completion times (inf for empty schedules)."""
    tkw, tkv, tkf, cap, tk1 = params
    n = len(seqs)
    n_eng = len(tk1)
    order = list(np.argsort(arrivals, kind="stable"))
    next_arr = 0
    queue: list[int] = []     # FIFO, arrival order
    active: dict[int, list] = {}   # req -> [engine, remaining, stage_idx]
    free_slots = int(capacity)
    done = np.full(n, np.inf)
    t = 0.0

    def rates() -> np.ndarray:
        occ = np.zeros(n_eng)
        for e, _, _ in active.values():
            occ[e] += 1.0
        r = np.ones(n_eng)
        for e in range(n_eng):
            if occ[e] > 0:
                b = min(occ[e], cap[e])
                sb = max(tkw[e] + tkv[e] * b, tkf[e] * b)
                r[e] = (b / occ[e]) * (tk1[e] / sb)
        return r

    def start(i: int, k: int) -> None:
        e, w = seqs[i][k]
        active[i] = [e, w, k]

    while active or next_arr < n:
        r = rates()
        t_next = float("inf")
        for e, rem, _ in active.values():
            t_next = min(t_next, t + max(rem, 0.0) / r[e])
        if next_arr < n:
            t_next = min(t_next, float(arrivals[order[next_arr]]))
        for st in active.values():
            st[1] -= (t_next - t) * r[st[0]]
        t = t_next
        # completions first (freed slots admit the queue), then arrivals
        for i in sorted(i for i, st in active.items() if st[1] <= 1e-9):
            k = active[i][2]
            if k + 1 < len(seqs[i]):
                start(i, k + 1)
            else:
                del active[i]
                done[i] = t
                if queue:
                    start(queue.pop(0), 0)
                else:
                    free_slots += 1
        while next_arr < n and arrivals[order[next_arr]] <= t:
            i = order[next_arr]
            next_arr += 1
            if not seqs[i]:
                done[i] = float(arrivals[i])
                continue
            if free_slots > 0:
                free_slots -= 1
                start(i, 0)
            else:
                queue.append(i)
    return done


def _fleet_models(trie) -> tuple[list[str], dict[str, EngineTokenModel]]:
    engines = sorted({m.engine for m in trie.template.models})
    # 8 GiB KV budget: every arch lands a cap well above 1 (a cap-1
    # engine degenerates to exact 1/n timesharing — indistinguishable
    # from the scalar knee, which would void the estimation-error gate)
    tms = {
        e: EngineTokenModel.from_roofline(
            e, get_config(ENGINE_ARCHS[i % len(ENGINE_ARCHS)]),
            context_len=2048, kv_budget_bytes=8 << 30)
        for i, e in enumerate(engines)
    }
    return engines, tms


def run(wf: str | None = None, tiny: bool = False,
        n_requests: int | None = None, capacity: int | None = None):
    wf = wf or ("nl2sql_2" if tiny else "nl2sql_8")
    n_requests = n_requests or (48 if tiny else 160)
    capacity = capacity or (16 if tiny else 32)
    t_total = time.perf_counter()

    rows = _curve_rows(CURVE_ARCHS_TINY if tiny else CURVE_ARCHS_FULL)
    curve_max_err = max(r["rel_err"] for r in rows)

    trie, wl = workload(wf)
    ann = exact_ann(wf)
    engines, tms = _fleet_models(trie)
    eng_idx = {e: j for j, e in enumerate(engines)}
    eng_of_model = [m.engine for m in trie.template.models]
    stage_tokens = wl.stage_tokens_fn()

    # token work table (batch-1 seconds) over the whole workload: the
    # shared ground-truth work quanta for BOTH lanes, the scalar lane's
    # mean-service calibration, and the nominal-rate normalizer
    step1 = np.array([max(tms[e].t_weights_s + tms[e].t_kv_s,
                          tms[e].t_flop_s) for e in engines])
    pref = np.array([tms[e].prefill_tok_s for e in engines])
    m2e = np.array([eng_idx[e] for e in eng_of_model])
    work_tab = 256.0 * pref[m2e][None, None, :] \
        + wl.tokens * step1[m2e][None, None, :]
    mean_service = {
        e: float(np.mean(work_tab[:, :, m2e == j]))
        for j, e in enumerate(engines)
    }
    wm = TokenWorkModel(engines=tms, mean_service_s=mean_service,
                        stage_tokens=stage_tokens)
    # the scalar approximation of the SAME engines: free up to the KV
    # cap, timeshare above it — no sub-cap batching stretch
    scalar = FleetLoadModel(
        engines={e: EngineLoadModel(
            e, concurrency=int(tms[e].kv_capacity), jitter=0.0)
            for e in engines},
        mean_service_s=mean_service,
    )

    base_exec = make_workload_executor(wl)

    def execu(q: int, d: int, m: int, t_now: float):
        # both lanes run the same token-grounded unloaded work; only the
        # engine calendar (token curve vs scalar knee) differs
        s, c, _ = base_exec(q, d, m, t_now)
        p, dk = stage_tokens(q, d, m)
        return s, c, wm.work_of(eng_of_model[m], p, dk)

    obj = Objective(
        "max_acc",
        cost_cap=float(np.quantile(ann.cost[trie.terminal], 0.5)),
    )
    reqs = np.random.default_rng(0).choice(wl.n_requests, n_requests,
                                           replace=True)
    # nominal fleet service rate: capacity slots working off requests of
    # ~D/2 mean stages at the mean token work — the load factors sweep
    # around it so the knee lands mid-sweep at any roofline timescale
    depth = wl.S.shape[1]
    nominal = capacity / (float(np.mean(work_tab)) * (depth * 0.5 + 1.0))
    factors = LOAD_FACTORS_TINY if tiny else LOAD_FACTORS_FULL
    rates = tuple(round(f * nominal, 6) for f in factors)

    params = (np.array([tms[e].t_weights_s for e in engines]),
              np.array([tms[e].t_kv_s for e in engines]),
              np.array([tms[e].t_flop_s for e in engines]),
              np.array([tms[e].kv_capacity for e in engines]),
              step1)

    def replay_p99(results, arr):
        """Token-physics ground-truth p99 of a lane's realized schedule."""
        seqs = []
        for i, r in enumerate(results):
            if r.outcome != "served":
                seqs.append([])
                continue
            q = int(reqs[i])
            seqs.append([
                (int(m2e[m]), wm.work_of(eng_of_model[m],
                                         *stage_tokens(q, k, m)))
                for k, m in enumerate(r.models)
            ])
        done = _token_replay(arr, seqs, params, capacity)
        served = np.array([r.outcome == "served" for r in results])
        return float(np.percentile((done - arr)[served], 99))

    def lane(arr, compiled, tokens):
        kw = (dict(work_model=wm) if tokens
              else dict(fleet_load=scalar))
        return run_events(trie, ann, obj, reqs, execu, arrivals=arr,
                          capacity=capacity, policy="dynamic_load_aware",
                          compiled=compiled, **kw)

    # warm every lane once (one XLA program each for the planner and the
    # two engine configs) so the retrace pins below see steady state
    warm_arr = poisson_arrivals(n_requests, rates[0], seed=1)
    for tokens in (True, False):
        lane(warm_arr, False, tokens)
        lane(warm_arr, True, tokens)
    pc0 = fleet_planner_cache_size()
    ec0 = compiled_engine_cache_size()

    err_tok_sum = 0.0
    err_scalar_sum = 0.0
    for rate, factor in zip(rates, factors):
        arr = poisson_arrivals(n_requests, rate, seed=1)
        res_t, stats_t = lane(arr, False, True)
        cres_t, _ = lane(arr, True, True)
        if any(a.outcome != b.outcome or a.models != b.models
               or a.total_lat != b.total_lat
               for a, b in zip(res_t, cres_t)):
            raise RuntimeError(
                f"compiled token calendar disagrees with the host loop "
                f"at rate={rate}/s — run the differential oracle suite")
        res_s, _ = lane(arr, False, False)

        served_t = np.array([r.outcome == "served" for r in res_t])
        served_s = np.array([r.outcome == "served" for r in res_s])
        p99_est_t = float(np.percentile(
            [r.total_lat for r, ok in zip(res_t, served_t) if ok], 99))
        p99_est_s = float(np.percentile(
            [r.total_lat for r, ok in zip(res_s, served_s) if ok], 99))
        p99_true_t = replay_p99(res_t, arr)
        p99_true_s = replay_p99(res_s, arr)
        err_t = abs(p99_est_t - p99_true_t)
        err_s = abs(p99_est_s - p99_true_s)
        err_tok_sum += err_t
        err_scalar_sum += err_s
        rows.append({
            "kind": "p99", "workflow": wf, "load_factor": factor,
            "rate_rps": rate,
            "p99_tokens_s": round(p99_est_t, 4),
            "p99_tokens_true_s": round(p99_true_t, 4),
            "p99_err_tokens_s": round(err_t, 6),
            "p99_scalar_s": round(p99_est_s, 4),
            "p99_scalar_true_s": round(p99_true_s, 4),
            "p99_err_scalar_s": round(err_s, 6),
            "events": stats_t.events,
            "replans": stats_t.replans,
            "mean_queue_wait_s": round(stats_t.mean_queue_wait_s, 3),
        })

    pc1, ec1 = fleet_planner_cache_size(), compiled_engine_cache_size()
    if pc0 >= 0 and pc1 != pc0:
        raise RuntimeError(
            f"fleet planner re-traced {pc1 - pc0} times across the token "
            "sweep — the token work model must not perturb the planner's "
            "compiled batch shapes")
    if ec0 >= 0 and ec1 != ec0:
        raise RuntimeError(
            f"compiled engine re-traced {ec1 - ec0} times across the "
            "token sweep — the token operands must stay traced buffers, "
            "not static config")
    if not err_tok_sum < err_scalar_sum:
        raise RuntimeError(
            f"token calendar did not beat the scalar model: p99 "
            f"estimation error {err_tok_sum:.4f}s (tokens) vs "
            f"{err_scalar_sum:.4f}s (scalar) summed over load factors "
            f"{factors} — the whole point of ISSUE 10 is that it must")

    elapsed = time.perf_counter() - t_total
    save_report("BENCH_token_calendar", rows)
    return {
        "name": "token_calendar",
        "us_per_call": elapsed * 1e6 / max(len(rows), 1),
        "derived": (f"curve_max_err={curve_max_err * 100:.2f}% "
                    f"p99_err_tokens={err_tok_sum:.3f}s "
                    f"p99_err_scalar={err_scalar_sum:.3f}s retraces=0"),
        "rows": rows,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: small trie, 2 load factors, 2 archs")
    ap.add_argument("--workflow", default=None)
    args = ap.parse_args()
    out = run(wf=args.workflow, tiny=args.tiny)
    for r in out["rows"]:
        if r["kind"] == "curve":
            print(f"curve {r['arch']:22s} b={r['batch']:4d} "
                  f"sim={r['sim_tok_s']:10.1f} tok/s "
                  f"analytic={r['analytic_tok_s']:10.1f} tok/s "
                  f"err={r['rel_err'] * 100:.2f}%")
        else:
            print(f"p99   load={r['load_factor']:4.2f}x "
                  f"rate={r['rate_rps']:.4f}/s "
                  f"tokens={r['p99_tokens_s']:9.2f}s "
                  f"(err {r['p99_err_tokens_s']:.4f}s) "
                  f"scalar={r['p99_scalar_s']:9.2f}s "
                  f"(err {r['p99_err_scalar_s']:.4f}s)")
    print(out["derived"])


if __name__ == "__main__":
    enable_compile_cache()
    main()
