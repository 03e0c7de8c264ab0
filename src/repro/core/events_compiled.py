"""Jitted epoch-batched event engine: the compiled virtual clock.

`repro.core.events.run_events` drives its virtual clock from Python: every
arrival/completion/deadline event pays a host round-trip even though the
replan (PR 4) and the planner's slot state already live on the device.
This module compiles the clock itself: **all events inside a time epoch
run in one jitted step** — a `lax.while_loop` whose body replicates the
host loop's per-timestamp contract exactly (completions, deadline sheds,
arrivals, queue rejections, then the preempt/admit/replan/dispatch cycle)
over fixed-capacity device arrays.  The host merely feeds epoch
boundaries and drains O(1) scalars per epoch, so a million-request trace
replays in constant host memory (`repro.core.streaming` accumulators are
folded inside the traced step).

Architecture (see docs/EVENT_ENGINE.md for the full design):

- **epoch segmentation**: arrivals are sorted once; the host advances a
  cursor ``chunk`` arrivals at a time and calls the jitted ``step(state,
  consts, t_hi)`` with ``t_hi`` = the last arrival time of the chunk (the
  final epoch uses +inf).  ``t_hi`` is a *traced* operand, so varying
  epoch widths never retrace — one compilation per static configuration,
  cached module-wide in `_ENGINE_CACHE`.
- **traced state**: every mutable quantity of the host loop is a device
  array in one state pytree — slot columns, the `FleetEngineSim` calendar
  columns (drained via `repro.serving.loadsim.traced_advance`), per-class
  FIFO rings over a precomputed arrival-order table, a fixed-capacity
  paused buffer for preempted work, per-request outputs, and the
  streaming accumulators.  The admission queue is not a heap: within a
  class, priority order IS arrival order, so a (head, tail) ring per
  class plus an unrolled K-way merge by (class weight, arrival seq)
  reproduces the host heap's pop order exactly.
- **bit-compatibility**: the engine runs under a scoped
  ``jax.enable_x64`` so all clock/work arithmetic is float64
  with the same op order as the host's numpy (the planner kernel stays
  explicitly float32 on both paths).  The differential oracle
  (`tests/test_oracle_differential.py`, ``engine="compiled"`` lane) pins
  outcome/cost/completion-time equality over the deterministic sweep.

Restrictions vs the host loop (all raise `NotImplementedError`): stage
executors must be *pure functions of (request value, depth, model)* — the
engine tabulates them once up front — and only the stock admission
policies, `FleetLoadModel` load coupling, and ``load_probe=None`` are
supported.  Custom duck-typed policies/sims/probes keep using the host
loop.  ``replan_overhead_s`` and `EventStats.replan_s` are host-loop
wall-clock concepts and are reported as zero/empty here.  The online
estimator ``refresh`` loop also stays host-side (posterior updates need
per-completion service observations) — precomputed
``annotation_schedule`` swaps and the ``explore`` lane ARE supported and
bit-compatible with the host loop.

Fault injection (`repro.core.faults.FaultSchedule`, ISSUE 9) is
supported for engine outages and seeded/forced stage failures with
checkpointed recovery: fault transition times, the per-(request, depth,
attempt) failure draws and the backoff table are traced operands, the
availability mask is an epoch state column, and the planner's
``blocked_depth`` node column is recomputed in-trace — outage/recovery
flips compile ZERO new programs.  The host-only corners raise
`NotImplementedError`: ``timeout_k`` (the forecast-armed cancellation is
a host-side scheduler concept here), ``recovery="restart"`` (the naive
baseline lane of `benchmarks/chaos.py`), and faults combined with
predictive/cost-aware admission (their displaced-work forecast inflation
and the downgrade lane's host-side min-cost search cannot see the
availability mask).

Tracing (docs/EVENT_ENGINE.md, "Spans, scopes and counters"): every
event phase of the step runs under one of four named scopes (`SCOPES`),
which only annotate the compiled program's HLO metadata; under the token
calendar its rates, advance and delay row run under a fifth,
`CALENDAR_SCOPE`, nested in the clock's and the dispatch's.  Every call
opens six host spans ``vinelm.build`` ... ``vinelm.drain``, recorded
while a `jax.profiler` session is open.  The host phases' wall times, the
count of width-1 planner sweeps and of step dispatches are counted in
every call (`EventStats.host_s`/``sweeps``/``epochs`` and the streamed
summary), and under the token calendar the engine-seconds spent batching
under a KV cap and above one (``batched_s``, ``over_cap_s``).
`engine_scope_maps` joins a device trace's operation names to the
scopes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import re
import time
import warnings
from typing import Callable

import numpy as np

from repro.core.admission import (
    FAILED,
    REJECTED,
    SERVED,
    SHED,
    TracedAdmission,
    _subtree_reductions,
    get_policy,
    traced_admission,
)
from repro.core.controller import Objective
from repro.core.controller_jax import (
    TrieDevice,
    _resolve_variant,
    objective_scalars,
    traced_fleet_plan,
    trie_engines,
)
from repro.core.events import _DEFAULT_CAPACITY, EventStats, _explore_tables
from repro.core.runtime import ExecutionResult, StageExecutor
from repro.core.streaming import QuantileSketch, welford_merge
from repro.core.trie import Trie, TrieAnnotations
from repro.kernels.ops import PLAN_SCOPE, trie_plan_tiles

# outcome codes inside the traced state (host strings on the way out)
_OC_SERVED, _OC_REJECTED, _OC_SHED, _OC_FAILED = 0, 1, 2, 3
_OUTCOMES = {_OC_SERVED: SERVED, _OC_REJECTED: REJECTED, _OC_SHED: SHED,
             _OC_FAILED: FAILED}
_CERT_SLACK = 1e-9   # deadline-shed certainty slack (events.py step 1b/2b)
_DONE_TOL = 1e-9     # FleetEngineSim._DONE_TOL
_SLO_TOL = 1e-9      # run_events' final SLO check tolerance

DEFAULT_EPOCH = 1024  # arrivals per jitted step (throughput knob, not math)

# the step's named scopes: the event loop with its float64 clock and
# calendar drain; queue, admission, preemption and the replan cycle's
# loop; the replan round and dispatch outside the sweeps; the width-1
# sweeps themselves (`repro.kernels.ops.trie_plan`)
SCOPES = ("vinelm/clock", "vinelm/admit", "vinelm/dispatch", PLAN_SCOPE)
_CLOCK, _ADMIT, _DISPATCH, _ = SCOPES
# the token calendar's rates, advance and planner delay row (token mode
# only), nested in the clock's or the dispatch's scope where each runs
CALENDAR_SCOPE = "vinelm/calendar"


@dataclasses.dataclass(frozen=True)
class _EngineConfig:
    """Static specialization key of one compiled engine program.

    Everything here changes the traced program structure; everything that
    merely changes *values* (arrival times, work tables, deadlines,
    objective scalars) is a traced operand instead, so replaying a new
    trace through the same configuration hits the cache."""

    capacity: int
    n_classes: int
    n_engines: int
    n_models: int
    max_depth: int
    priorities: bool
    preempt: bool
    ps: bool               # processor-sharing calendar (vs unit-rate)
    load_aware: bool
    deadline_sheds: bool
    pol: TracedAdmission
    kind: str
    kind_dg: str           # downgrade-lane objective kind (cost_aware)
    variant: str
    n_bins: int            # streaming histogram bins (incl. under/overflow)
    n_shards: int = 1      # lane-axis mesh extent (1 = single device)
    explore: bool = False  # epsilon-greedy exploration lane (ISSUE 8)
    # token-level calendar (ISSUE 10): job rates come from the continuous-
    # batching decode-step throughput curve + KV cap instead of the PS
    # concurrency knee; implies cfg.ps (remaining work tracked in jrm).
    # The curve parameters themselves are traced operands (cn["tkw"] ...).
    tokens: bool = False
    # fault injection (ISSUE 9): outage transitions and/or stage-failure
    # draws change the traced program; the schedule itself is operands
    fault_outages: bool = False
    fault_failures: bool = False
    max_retries: int = 0   # retry budget (exhaustion compare is traced-free)
    paused_cap: int = 0    # paused-buffer rows per class (C normally; B
    #                        under outages, whose victims can stack past C)


_ENGINE_CACHE: dict[_EngineConfig, Callable] = {}
# (config, operand structure, operand avals) -> the step program, for
# every specialization that ran, so `engine_scope_maps` can lower it again
_ENGINE_CALLS: dict[tuple, Callable] = {}


def compiled_engine_cache_size() -> int:
    """Total compiled specializations across every engine program this
    process traced, or -1 when the JAX runtime doesn't expose the counter
    — the zero-retrace guard the tests pin: epoch width, trace content,
    deadlines, and objective scalars are all traced operands, so replaying
    new traces through a known configuration must not grow this."""
    total = 0
    for fn in _ENGINE_CACHE.values():
        try:
            total += fn._cache_size()
        except Exception:
            return -1
    return total


def _call_signature(cfg: _EngineConfig, st, cn) -> tuple:
    import jax

    leaves, tree = jax.tree.flatten((st, cn))
    return cfg, tree, tuple(jax.typeof(x) for x in leaves)


def engine_scope_maps() -> list[dict[str, str]]:
    """One map per compiled engine program this process has called: HLO
    instruction name (``while.404``, as a device trace names the
    operation) -> its ``op_name`` scope path
    (``jit(step)/while/body/vinelm/dispatch/...``).

    Built only on request: each program is lowered again from its
    operands' shapes and compiled (a hit in JAX's persistent compilation
    cache where one is on), and its text parsed by `hlo_scope_map`.  Call
    it after the calls it describes."""
    import jax

    maps = []
    for (_, tree, avals), step in _ENGINE_CALLS.items():
        args = jax.tree.unflatten(tree, [
            jax.ShapeDtypeStruct(a.shape, a.dtype, weak_type=a.weak_type)
            for a in avals])
        with jax.enable_x64(True):
            text = step.lower(*args, 0.0).compile().as_text()
        maps.append(hlo_scope_map(text))
    return maps


_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?(\S+) \(.*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+) = ")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLEE = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation"
                         r"|false_computation)=%?([\w.\-]+)")
_HLO_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def hlo_scope_map(text: str) -> dict[str, str]:
    """Instruction name -> ``op_name`` of a compiled HLO module's text.

    An instruction without metadata (the loop-carried tuples and copies
    XLA adds) takes the ``op_name`` of the instruction that calls its
    computation, the while, conditional or fusion it runs inside."""
    own, comp_of, caller = {}, {}, {}
    comp = None
    for line in text.splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _HLO_INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        comp_of[name] = comp
        op = _HLO_OP_NAME.search(line)
        if op:
            own[name] = op.group(1)
        callees = _HLO_CALLEE.findall(line)
        for branches in _HLO_BRANCHES.findall(line):
            callees += [c.strip().lstrip("%") for c in branches.split(",")]
        for c in callees:
            caller[c] = name
    out = {}
    for name in comp_of:
        seen, cur = set(), name
        while cur not in own and cur not in seen:
            seen.add(cur)
            cur = caller.get(comp_of[cur])
            if cur is None:
                break
        if cur is not None and cur in own:
            out[name] = own[cur]
    return out


@contextlib.contextmanager
def _host_span(host_s: dict, phase: str, **meta):
    """One host phase of a call: the profiler span ``vinelm.<phase>``
    (written only while a profiler session is open) around the block,
    whose wall time is always added to ``host_s[phase]``."""
    import jax

    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(f"vinelm.{phase}", **meta) as span:
        yield span
    host_s[phase] = host_s.get(phase, 0.0) + time.perf_counter() - t0


def _build_step(cfg: _EngineConfig):
    """Trace-and-cache the jitted epoch step for one static config."""
    if cfg in _ENGINE_CACHE:
        return _ENGINE_CACHE[cfg]

    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.dist.sharding import LANE_AXIS
    from repro.serving.loadsim import traced_advance, traced_engine_rates, \
        traced_job_rates, traced_token_rates

    C, K, E, M = cfg.capacity, cfg.n_classes, cfg.n_engines, cfg.n_models
    P = cfg.paused_cap
    # the paused buffer exists for priority preemption AND for outage
    # checkpoints (stage model -1 = replan on admit), so every structural
    # gate on its presence keys off this union, not cfg.priorities alone
    paused_on = cfg.priorities or cfg.fault_outages
    fault_any = cfg.fault_outages or cfg.fault_failures
    pol = cfg.pol
    i32 = jnp.int32

    def scat_set(dst, idx, val, mask):
        """Masked scatter into a (B,)-indexed array (drop when ~mask)."""
        B = dst.shape[0]
        return dst.at[jnp.where(mask, idx, B)].set(val, mode="drop")

    def scat_add(dst, idx, val, mask):
        B = dst.shape[0]
        return dst.at[jnp.where(mask, idx, B)].add(val, mode="drop")

    def wmerge(wt, cnt, mean, m2):
        """Fold a batch (count, mean, M2) into running Welford state —
        Chan's parallel merge, trace-safe (no data-dependent branches)."""
        c0, m0, s0 = wt
        tot = c0 + cnt
        tot_s = jnp.where(tot > 0, tot, 1.0)
        d = mean - m0
        m = m0 + d * cnt / tot_s
        s = s0 + m2 + d * d * c0 * cnt / tot_s
        keep = cnt > 0
        return (jnp.where(keep, tot, c0), jnp.where(keep, m, m0),
                jnp.where(keep, s, s0))

    def batch_stats(x, mask):
        cnt = jnp.sum(jnp.where(mask, 1.0, 0.0))
        mean = jnp.sum(jnp.where(mask, x, 0.0)) / jnp.where(cnt > 0, cnt, 1.0)
        m2 = jnp.sum(jnp.where(mask, (x - mean) ** 2, 0.0))
        return cnt, mean, m2

    def record_terminal(st, cn, req, valid, t, outcome, cost):
        """Every terminal disposition funnels through here: outputs,
        done-counter, and the streaming accumulators (latency/cost moments
        and the quantile histogram over SERVED requests, SLO-violation
        count over all terminal requests)."""
        B = st["roc"].shape[0]
        reqc = jnp.clip(req, 0, B - 1)
        st = dict(st)
        st["roc"] = scat_set(st["roc"], req, outcome, valid)
        st["rdn"] = scat_set(st["rdn"], req, t, valid)
        st["rct"] = scat_set(st["rct"], req, cost, valid)
        st["don"] = st["don"] + jnp.sum(jnp.where(valid, 1, 0))
        lat = t - cn["arr"][reqc]
        served = valid & (outcome == _OC_SERVED)
        st["lw"] = wmerge(st["lw"], *batch_stats(lat, served))
        st["cw"] = wmerge(st["cw"], *batch_stats(cost, served))
        bins = jnp.searchsorted(cn["edges"], lat, side="right")
        st["hist"] = st["hist"].at[jnp.where(
            served, bins, cfg.n_bins)].add(1, mode="drop")
        cap = cn["cap"][reqc]
        st["slo"] = st["slo"] + jnp.sum(jnp.where(
            valid & jnp.isfinite(cap) & (lat > cap + _SLO_TOL), 1, 0))
        return st

    def release(st, mask):
        """Host `release_slot` over a (C,) mask: every per-slot column."""
        out = {**st,
               "so": jnp.where(mask, -1, st["so"]),
               "su": jnp.where(mask, 0, st["su"]),
               "sec": jnp.where(mask, 0.0, st["sec"]),
               "sm": jnp.where(mask, -1, st["sm"]),
               "sdg": jnp.where(mask, False, st["sdg"]),
               "sddl": jnp.where(mask, jnp.inf, st["sddl"]),
               "sfree": st["sfree"] | mask}
        if cfg.fault_failures:
            out["srt"] = jnp.where(mask, jnp.inf, st["srt"])
        return out

    def sim_clear(st, mask):
        """`FleetEngineSim._clear` over a (C,) mask."""
        return {**st,
                "je": jnp.where(mask, -1, st["je"]),
                "jtc": jnp.where(mask, jnp.inf, st["jtc"]),
                "jwk": jnp.where(mask, 0.0, st["jwk"]),
                "jrm": jnp.where(mask, jnp.inf, st["jrm"]),
                "jw": jnp.where(mask, 1.0, st["jw"])}

    def remaining_col(st, t):
        """`FleetEngineSim.remaining(t)`: (C,) unloaded seconds, inf idle.
        The calendar was already advanced to t at the event's start."""
        act = st["je"] >= 0
        rem = jnp.maximum(st["jrm"], 0.0) if cfg.ps \
            else jnp.maximum(st["jtc"] - t, 0.0)
        return jnp.where(act, rem, jnp.inf)

    def job_rates(st, cn):
        act = st["je"] >= 0
        occ = jnp.zeros(E + 1, st["jrm"].dtype).at[
            jnp.where(act, jnp.clip(st["je"], 0, E - 1), E)].add(
            jnp.where(act, 1.0, 0.0))[:E]
        if cfg.tokens:
            with jax.named_scope(CALENDAR_SCOPE):
                rates = traced_token_rates(occ, cn["tkw"], cn["tkv"],
                                           cn["tkf"], cn["tkc"], cn["tk1"])
        else:
            rates = traced_engine_rates(occ, cn["conc"])
        return traced_job_rates(st["je"], st["jw"], act, rates, st["wtd"])

    def next_completion(st, cn):
        """`FleetEngineSim.next_completion` — the per-job quotient form,
        value-equal to the host's per-engine min (division by the shared
        positive rate commutes with min exactly in IEEE)."""
        act = st["je"] >= 0
        if not cfg.ps:
            return jnp.min(jnp.where(act, st["jtc"], jnp.inf))
        jr = job_rates(st, cn)
        q = jnp.where(act, jnp.maximum(st["jrm"], 0.0)
                      / jnp.where(act, jr, 1.0), jnp.inf)
        return jnp.where(act.any(), st["tl"] + jnp.min(q), jnp.inf)

    def calendar_seconds(st, cn, t, act):
        """Engine-seconds of the advance to ``t`` spent batching (1 <
        occupancy <= KV cap) and above a KV cap, added engine by engine
        to the ``bts``/``ocs`` counters."""
        dt = t - st["tl"]
        occ = jnp.zeros(E + 1, dt.dtype).at[
            jnp.where(act, jnp.clip(st["je"], 0, E - 1), E)].add(
            jnp.where(act, 1.0, 0.0))[:E]
        over = (dt > 0.0) & (occ > cn["tkc"])
        batched = (dt > 0.0) & (occ > 1.0) & ~over
        bts, ocs = st["bts"], st["ocs"]
        for e in range(E):
            bts = bts + jnp.where(batched[e], dt, 0.0)
            ocs = ocs + jnp.where(over[e], dt, 0.0)
        return {**st, "bts": bts, "ocs": ocs}

    def peak_update(st, cn):
        act = st["je"] >= 0
        occ = jnp.zeros(E + 1, jnp.int64).at[
            jnp.where(act, jnp.clip(st["je"], 0, E - 1), E)].add(
            jnp.where(act, 1, 0))[:E]
        return {**st, "po": jnp.maximum(st["po"], occ)}

    # ------------------------------------------------------------------
    # admission queue: per-class FIFO rings + paused buffer
    # ------------------------------------------------------------------
    def class_head(st, cn, k):
        """(valid, request, is_paused) head of class ``k`` (python int).

        Invariant: every paused seq in a class precedes every never-
        admitted seq (admission consumed the ring in seq order), so the
        class head is the paused buffer's front when non-empty, else the
        fresh ring's front.  Under predictive admission the fresh front
        is kept non-rejected by the skip-dead fixups."""
        fh = st["qh"][k]
        fresh_valid = fh < st["qt"][k]
        fresh_req = cn["members"][k, jnp.clip(fh, 0, cn["arr"].shape[0] - 1)]
        if paused_on:
            has_p = st["pn"][k] > 0
            return (has_p | fresh_valid,
                    jnp.where(has_p, st["pb"][k, 0], fresh_req), has_p)
        return fresh_valid, fresh_req, jnp.asarray(False)

    def merged_head(st, cn):
        """Queue head across classes: max class weight, then min arrival
        seq — exactly the host heap's (-weight, seq) pop order.  Returns
        (valid, class index, request, head weight)."""
        big = jnp.iinfo(jnp.int64).max
        best_k = jnp.asarray(-1, i32)
        best_w = jnp.asarray(-jnp.inf, st["sec"].dtype)
        best_s = jnp.asarray(big, jnp.int64)
        best_r = jnp.asarray(0, i32)
        for k in range(K):
            valid, req, _ = class_head(st, cn, k)
            s = jnp.where(valid, cn["seq"][req], big)
            w = jnp.where(valid, cn["wcls"][k], -jnp.inf)
            better = valid & ((w > best_w) | ((w == best_w) & (s < best_s)))
            best_k = jnp.where(better, k, best_k)
            best_w = jnp.where(better, w, best_w)
            best_s = jnp.where(better, s, best_s)
            best_r = jnp.where(better, req, best_r)
        return best_k >= 0, best_k, best_r, best_w

    def skip_dead(st, cn):
        """Advance each class's fresh head past predictive-rejected
        entries so `class_head` always exposes a live request."""
        if not pol.wants_forecast:
            return st
        B = cn["arr"].shape[0]
        for k in range(K):
            def cond(s, k=k):
                h = s["qh"][k]
                hr = cn["members"][k, jnp.clip(h, 0, B - 1)]
                return (h < s["qt"][k]) & s["dead"][hr]

            def body(s, k=k):
                return {**s, "qh": s["qh"].at[k].add(1)}

            st = lax.while_loop(cond, body, st)
        return st

    def pop_head(st, cn, k_idx):
        """Remove the merged head (class ``k_idx``, traced): paused front
        when present, else the fresh ring front."""
        onehot = jnp.arange(K) == k_idx
        if paused_on:
            from_p = onehot & (st["pn"] > 0)
            shifted = jnp.concatenate(
                [st["pb"][:, 1:], jnp.full((K, 1), -1, i32)], axis=1)
            st = {**st,
                  "pb": jnp.where(from_p[:, None], shifted, st["pb"]),
                  "pn": st["pn"] - from_p.astype(st["pn"].dtype),
                  "qh": st["qh"] + (onehot & ~from_p).astype(st["qh"].dtype)}
        else:
            st = {**st, "qh": st["qh"] + onehot.astype(st["qh"].dtype)}
        return skip_dead(st, cn)

    def paused_insert(st, cn, i, k_idx):
        """Insert request ``i`` into class ``k_idx``'s paused buffer in
        arrival-seq order (the host re-pushes it onto the heap; within a
        class the heap orders by seq)."""
        B = cn["arr"].shape[0]
        row = st["pb"][k_idx]
        iota = jnp.arange(P)
        seqs = jnp.where(iota < st["pn"][k_idx],
                         cn["seq"][jnp.clip(row, 0, B - 1)],
                         jnp.iinfo(jnp.int64).max)
        pos = jnp.sum(jnp.where(seqs < cn["seq"][i], 1, 0))
        new_row = jnp.where(iota < pos, row,
                            jnp.where(iota == pos, i, jnp.roll(row, 1)))
        return {**st,
                "pb": st["pb"].at[k_idx].set(new_row),
                "pn": st["pn"].at[k_idx].add(1),
                "rpp": st["rpp"].at[i].set(True)}

    def shed_paused_rows(st, cn, t, doom_fn):
        """Shed doomed entries out of every paused row (stable compaction),
        mirroring the host's queue-side paused-deadline sheds."""
        B = cn["arr"].shape[0]
        for k in range(K):
            row = st["pb"][k]
            iota = jnp.arange(P)
            activep = iota < st["pn"][k]
            req = jnp.clip(row, 0, B - 1)
            doomed = activep & doom_fn(req)
            ocp = jnp.full(P, _OC_SHED, i32)
            if fault_any:
                # a fault-touched request dies "failed", not "shed"
                flt = st["rfl"][req]
                ocp = jnp.where(flt, _OC_FAILED, ocp)
                st = record_terminal(st, cn, req, doomed, t, ocp,
                                     st["rpec"][req])
                st["ffc"] = st["ffc"] + jnp.sum(
                    jnp.where(doomed & flt, 1, 0))
                st["shd"] = st["shd"] + jnp.sum(
                    jnp.where(doomed & ~flt, 1, 0))
            else:
                st = record_terminal(st, cn, req, doomed, t, ocp,
                                     st["rpec"][req])
                st["shd"] = st["shd"] + jnp.sum(jnp.where(doomed, 1, 0))
            st["rpp"] = scat_set(st["rpp"], req, False, doomed)
            keep = activep & ~doomed
            tgt = jnp.where(keep, jnp.cumsum(keep, dtype=i32) - 1, P)
            new_row = jnp.full((P,), -1, i32).at[tgt].set(row, mode="drop")
            st["pb"] = st["pb"].at[k].set(new_row)
            st["pn"] = st["pn"].at[k].set(
                jnp.sum(keep).astype(st["pn"].dtype))
        return st

    def paused_doom(st, cn, t):
        def doom(req):
            ddl = cn["arr"][req] + cn["cap"][req]
            return jnp.isfinite(ddl) & (
                (t >= ddl) | (t + st["rprm"][req] > ddl + _CERT_SLACK))
        return doom

    def shed_oc(st, ownc):
        """(C,) outcome codes for a shed site: "failed" when any fault
        already touched the slot's owner (host `shed`), "shed" otherwise."""
        oc = jnp.full(C, _OC_SHED, i32)
        if fault_any:
            oc = jnp.where(st["rfl"][ownc], _OC_FAILED, oc)
        return oc

    def count_sheds(st, mask, ownc):
        """Mirror `shed_oc`'s split into the shd/ffc counters."""
        st = dict(st)
        if fault_any:
            flt = st["rfl"][ownc]
            st["ffc"] = st["ffc"] + jnp.sum(jnp.where(mask & flt, 1, 0))
            st["shd"] = st["shd"] + jnp.sum(jnp.where(mask & ~flt, 1, 0))
        else:
            st["shd"] = st["shd"] + jnp.sum(jnp.where(mask, 1, 0))
        return st

    # ------------------------------------------------------------------
    # event phases (the numbers mirror events.py's comments)
    # ------------------------------------------------------------------
    def phase_completions(st, cn, t):
        act = st["je"] >= 0
        done = act & ((st["jrm"] <= _DONE_TOL) if cfg.ps
                      else (st["jtc"] <= t))
        own = st["so"]
        newu = cn["child"][st["su"], jnp.clip(st["sm"], 0, M - 1)]
        st = dict(st)
        st["su"] = jnp.where(done, newu, st["su"])
        st["ru"] = scat_set(st["ru"], own, newu, done)
        st["sm"] = jnp.where(done, -1, st["sm"])
        fin = done & st["sok"]
        deep = done & ~st["sok"] & (cn["depth"][newu] >= cfg.max_depth)
        term = fin | deep
        st["rsc"] = scat_set(st["rsc"], own, True, fin)
        st = record_terminal(st, cn, own, term, t,
                             jnp.full(C, _OC_SERVED, i32), st["sec"])
        st["snd"] = st["snd"] | (done & ~term)
        st = release(st, term)
        return sim_clear(st, done)

    def phase_deadline_sheds(st, cn, t):
        if not cfg.deadline_sheds:
            return st
        B = cn["arr"].shape[0]
        # (i) certainty bound on in-service work: PS rate <= 1, so
        # t + remaining lower-bounds completion
        insvc = (st["so"] >= 0) & (st["sm"] >= 0)
        rem = remaining_col(st, t)
        ownc = jnp.clip(st["so"], 0, B - 1)
        ddl = cn["arr"][ownc] + cn["cap"][ownc]
        doomed = insvc & ((t >= ddl) | (t + rem > ddl + _CERT_SLACK))
        st = record_terminal(st, cn, st["so"], doomed, t,
                             shed_oc(st, ownc), st["sec"])
        st = dict(st)
        st = count_sheds(st, doomed, ownc)
        st = sim_clear(st, doomed)
        st = release(st, doomed)
        # (ii) backstop: the deadline column is a scheduled event (it also
        # catches slots held in a fault-retry backoff, whose stage column
        # is idle but whose deadline keeps ticking)
        mask2 = st["sddl"] <= t
        ownc2 = jnp.clip(st["so"], 0, B - 1)
        st["snd"] = st["snd"] & ~mask2
        st = record_terminal(st, cn, st["so"], mask2, t,
                             shed_oc(st, ownc2), st["sec"])
        st = count_sheds(st, mask2, ownc2)
        st = sim_clear(st, mask2 & (st["sm"] >= 0))
        return release(st, mask2)

    def phase_faults(st, cn, t):
        """Host step 1f: engine fault transitions at exactly t (their
        times force their own clock events, so transitions apply at
        t == fault time — unlike annotation swaps' strictly-past rule;
        downs before ups at one instant, per `FaultSchedule.events`).
        A down transition checkpoints every in-service stage on the dead
        engine into the paused buffer with stage model -1 ("replan on
        admit"), charging one retry attempt; an exhausted budget fails
        the request terminally.  Preempted stages whose paused calendar
        entry sat on the dead engine convert to replan-on-admit with the
        attempt charged but no exhaustion check (the host's lenient
        rule).  The availability mask ``av`` feeds the in-trace
        blocked-depth recompute at the next replan."""
        if not cfg.fault_outages:
            return st
        B = cn["arr"].shape[0]
        F = cn["ftt"].shape[0] - 1  # trailing +inf pad

        def cond(s):
            return cn["ftt"][jnp.clip(s["fi"], 0, F)] <= t

        def body(s):
            cur = jnp.clip(s["fi"], 0, F)
            ei = cn["fte"][cur]
            up = cn["ftu"][cur]
            s = dict(s)
            s["fi"] = s["fi"] + 1
            s["av"] = s["av"].at[ei].set(up)
            s["frc"] = s["frc"] + jnp.where(up, 1, 0)
            s["foc"] = s["foc"] + jnp.where(up, 0, 1)

            def hit_mask(s2):
                insvc = (s2["so"] >= 0) & (s2["sm"] >= 0)
                return insvc & (cn["eom"][jnp.clip(s2["sm"], 0, M - 1)]
                                == ei)

            def vbody(s2):
                # victims checkpoint one at a time in ascending slot
                # order (paused_insert is a sequential buffer mutation —
                # same order as the host's nonzero() sweep)
                hit = hit_mask(s2)
                slot = jnp.argmax(hit)
                onehot_c = jnp.arange(C) == slot
                i = s2["so"][slot]
                d = cn["depth"][s2["su"][slot]]
                ec = s2["sec"][slot]
                dg = s2["sdg"][slot]
                uu = s2["su"][slot]
                s2 = dict(s2)
                s2["fck"] = s2["fck"] + 1
                s2["rfl"] = s2["rfl"].at[i].set(True)
                s2["rpat"] = s2["rpat"].at[i, d].add(1)
                exhausted = s2["rpat"][i, d] > cfg.max_retries
                s2 = sim_clear(s2, onehot_c)

                def fail_out(ss):
                    ss = record_terminal(
                        ss, cn, jnp.full(1, i, i32), jnp.full(1, True), t,
                        jnp.full(1, _OC_FAILED, i32), jnp.full(1, ec))
                    ss = dict(ss)
                    ss["ffc"] = ss["ffc"] + 1
                    return ss

                def checkpoint(ss):
                    ss = dict(ss)
                    ss["rpu"] = ss["rpu"].at[i].set(uu)
                    ss["rpm"] = ss["rpm"].at[i].set(-1)
                    ss["rpok"] = ss["rpok"].at[i].set(False)
                    ss["rprm"] = ss["rprm"].at[i].set(0.0)
                    ss["rpec"] = ss["rpec"].at[i].set(ec)
                    ss["rpdg"] = ss["rpdg"].at[i].set(dg)
                    return paused_insert(ss, cn, i, cn["cls"][i])

                s2 = lax.cond(exhausted, fail_out, checkpoint, s2)
                return release(s2, onehot_c)

            def on_down(s2):
                s2 = lax.while_loop(lambda ss: hit_mask(ss).any(),
                                    vbody, s2)
                if cfg.priorities:
                    conv = s2["rpp"] & (s2["rpm"] >= 0) & (
                        cn["eom"][jnp.clip(s2["rpm"], 0, M - 1)] == ei)
                    dconv = jnp.clip(cn["depth"][s2["rpu"]], 0,
                                     cfg.max_depth - 1)
                    idx = jnp.arange(B)
                    s2 = dict(s2)
                    s2["rfl"] = s2["rfl"] | conv
                    s2["rpat"] = s2["rpat"].at[
                        jnp.where(conv, idx, B), dconv].add(1, mode="drop")
                    s2["rpm"] = jnp.where(conv, -1, s2["rpm"])
                    s2["rprm"] = jnp.where(conv, 0.0, s2["rprm"])
                return s2

            return lax.cond(up, lambda ss: ss, on_down, s)

        return lax.while_loop(cond, body, st)

    def phase_retry_release(st, cn, t):
        """Host step 1r: slots whose retry backoff expired rejoin the
        replan set — the re-root routes the retry wherever the planner
        now prefers (including around a still-down engine)."""
        if not cfg.fault_failures:
            return st
        rel = st["srt"] <= t
        return {**st, "srt": jnp.where(rel, jnp.inf, st["srt"]),
                "snd": st["snd"] | rel}

    def phase_arrivals(st, cn, t):
        B = cn["arr"].shape[0]

        def cond(s):
            return (s["ap"] < B) & (
                cn["arrs"][jnp.clip(s["ap"], 0, B - 1)] <= t)

        def body(s):
            k = cn["clsord"][jnp.clip(s["ap"], 0, B - 1)]
            return {**s, "ap": s["ap"] + 1,
                    "qt": s["qt"].at[k].add(1)}

        return lax.while_loop(cond, body, st)

    def phase_queue_rejections(st, cn, t):
        if not (pol.gates or cfg.deadline_sheds):
            return st
        if not pol.wants_forecast:
            # paused entries die only by deadline (shed, not reject)
            if paused_on and cfg.deadline_sheds:
                st = shed_paused_rows(st, cn, t, paused_doom(st, cn, t))
            if not pol.gates:
                return st
            # rejection is a prefix of each class ring: elapsed decreases
            # along the ring while the class cap is constant
            B = cn["arr"].shape[0]
            for k in range(K):
                def cond(s, k=k):
                    h = s["qh"][k]
                    i = cn["members"][k, jnp.clip(h, 0, B - 1)]
                    cap = cn["cap"][i]
                    return (h < s["qt"][k]) & jnp.isfinite(cap) & (
                        t - cn["arr"][i]
                        > cap - pol.min_path_lat + pol.margin)

                def body(s, k=k):
                    i = cn["members"][k, jnp.clip(s["qh"][k], 0, B - 1)]
                    one = jnp.full(1, i, i32)
                    tt = jnp.full(1, True)
                    s = record_terminal(s, cn, one, tt, t,
                                        jnp.full(1, _OC_REJECTED, i32),
                                        jnp.zeros(1, s["sec"].dtype))
                    s["rad"] = s["rad"].at[i].set(t)
                    s["rej"] = s["rej"] + 1
                    return {**s, "qh": s["qh"].at[k].add(1)}

                st = lax.while_loop(cond, body, st)
            return st
        return predictive_scan(st, cn, t)

    def predictive_scan(st, cn, t):
        """Host 2b under predictive admission: one pass over the merged
        (class weight, arrival seq) queue order, handing the k-th *kept*
        entry behind the free slots the k-th projected completion —
        positions matter, so rejection is no longer a ring prefix and
        rejected entries are tombstoned in the ``dead`` mask instead."""
        B = cn["arr"].shape[0]
        n_free = jnp.sum(jnp.where(st["sfree"], 1, 0))
        act = st["je"] >= 0
        if cfg.ps:
            jr = job_rates(st, cn)
            tc = st["tl"] + jnp.maximum(st["jrm"], 0.0) \
                / jnp.where(act, jr, 1.0)
        else:
            tc = st["jtc"]
        proj = jnp.sort(jnp.where(act, tc, jnp.inf))
        nproj = jnp.sum(jnp.where(act, 1, 0))
        proj_last = proj[jnp.clip(nproj - 1, 0, C - 1)]

        big = jnp.iinfo(jnp.int64).max

        def heads(s):
            """Scan-local heads: paused cursor first (lower seqs), then
            the fresh cursor (skipping prior tombstones)."""
            out = []
            for k in range(K):
                if cfg.priorities:
                    on_p = s["ppi"][k] < s["pn"][k]
                    p_req = s["pb"][k, jnp.clip(s["ppi"][k], 0, P - 1)]
                else:
                    on_p = jnp.asarray(False)
                    p_req = jnp.asarray(0, i32)
                fh = s["pfh"][k]
                f_ok = fh < s["qt"][k]
                f_req = cn["members"][k, jnp.clip(fh, 0, B - 1)]
                valid = on_p | f_ok
                req = jnp.where(on_p, p_req, f_req)
                out.append((valid, req, on_p))
            return out

        def cond(s):
            any_v = jnp.asarray(False)
            for valid, _, _ in heads(s):
                any_v = any_v | valid
            return any_v

        def body(s):
            hs = heads(s)
            best_k = jnp.asarray(-1, i32)
            best_w = jnp.asarray(-jnp.inf, st["sec"].dtype)
            best_s = jnp.asarray(big, jnp.int64)
            best_r = jnp.asarray(0, i32)
            best_p = jnp.asarray(False)
            for k, (valid, req, on_p) in enumerate(hs):
                sq = jnp.where(valid, cn["seq"][req], big)
                w = jnp.where(valid, cn["wcls"][k], -jnp.inf)
                better = valid & ((w > best_w)
                                  | ((w == best_w) & (sq < best_s)))
                best_k = jnp.where(better, k, best_k)
                best_w = jnp.where(better, w, best_w)
                best_s = jnp.where(better, sq, best_s)
                best_r = jnp.where(better, req, best_r)
                best_p = jnp.where(better, on_p, best_p)
            i = best_r
            onehot = jnp.arange(K) == best_k
            # paused head: deadline-certainty shed or keep
            if cfg.priorities and cfg.deadline_sheds:
                doom_p = best_p & paused_doom(s, cn, t)(i)
            else:
                doom_p = jnp.asarray(False)
            # fresh head: forecast-gated rejection
            j = s["pos"] - n_free
            use_wf = (j >= 0) & (nproj > 0)
            nproj_s = jnp.where(nproj > 0, nproj, 1)
            g = (j // nproj_s).astype(st["sec"].dtype)
            rix = jnp.clip(j % nproj_s, 0, C - 1)
            wf = jnp.where(use_wf, jnp.maximum(
                0.0, proj[rix] - t + g * (proj_last - t)), 0.0)
            cap = cn["cap"][i]
            rej = ~best_p & jnp.isfinite(cap) & (
                t - cn["arr"][i] + pol.discount * wf
                > cap - pol.min_path_lat + pol.margin)
            kept = ~doom_p & ~rej
            one = jnp.full(1, i, i32)
            ec_term = jnp.where(doom_p, s["rpec"][i], 0.0) \
                if cfg.priorities else jnp.asarray(0.0, st["sec"].dtype)
            s = record_terminal(
                s, cn, one, jnp.full(1, doom_p | rej), t,
                jnp.full(1, jnp.where(doom_p, _OC_SHED, _OC_REJECTED), i32),
                jnp.full(1, ec_term))
            s["shd"] = s["shd"] + jnp.where(doom_p, 1, 0)
            s["rej"] = s["rej"] + jnp.where(rej, 1, 0)
            s["rad"] = scat_set(s["rad"], one, t, jnp.full(1, rej))
            if cfg.priorities:
                s["rpp"] = scat_set(s["rpp"], one, False,
                                    jnp.full(1, doom_p))
            s["dead"] = scat_set(s["dead"], one, True, jnp.full(1, rej))
            s["pos"] = s["pos"] + jnp.where(kept, 1, 0)
            # shed paused entries compact out of the buffer; the cursor
            # stays (the next entry slid into its position)
            if cfg.priorities:
                row = s["pb"][best_k]
                iota = jnp.arange(P)
                drop = best_p & doom_p
                comp = jnp.where((iota >= s["ppi"][best_k]) & drop,
                                 jnp.roll(row, -1), row)
                comp = comp.at[P - 1].set(
                    jnp.where(drop, -1, comp[P - 1]))
                s["pb"] = s["pb"].at[best_k].set(comp)
                s["pn"] = s["pn"] - (onehot & drop).astype(s["pn"].dtype)
                s["ppi"] = s["ppi"] + (onehot & best_p & ~doom_p).astype(
                    s["ppi"].dtype)
            s["pfh"] = s["pfh"] + (onehot & ~best_p).astype(s["pfh"].dtype)
            # fresh cursor skips tombstones from earlier events
            for k in range(K):
                def scond(ss, k=k):
                    h = ss["pfh"][k]
                    hr = cn["members"][k, jnp.clip(h, 0, B - 1)]
                    return (h < ss["qt"][k]) & ss["dead"][hr]

                def sbody(ss, k=k):
                    return {**ss, "pfh": ss["pfh"].at[k].add(1)}

                s = lax.while_loop(scond, sbody, s)
            return s

        st = dict(st)
        st["pos"] = jnp.asarray(0, jnp.int64)
        st["pfh"] = st["qh"]
        if cfg.priorities:
            st["ppi"] = jnp.zeros(K, i32)
        st = lax.while_loop(cond, body, st)
        st.pop("pos")
        st.pop("pfh")
        st.pop("ppi", None)
        return skip_dead(st, cn)

    def any_preemptable(st, cn):
        if not (cfg.priorities and cfg.preempt):
            return jnp.asarray(False)
        B = cn["arr"].shape[0]
        valid, _, _, head_w = merged_head(st, cn)
        insvc = (st["so"] >= 0) & (st["sm"] >= 0)
        lower = insvc & (cn["wreq"][jnp.clip(st["so"], 0, B - 1)] < head_w)
        return valid & lower.any()

    def phase_preempt(st, cn, t):
        if not (cfg.priorities and cfg.preempt):
            return st
        B = cn["arr"].shape[0]

        def cond(s):
            return ~s["sfree"].any() & any_preemptable(s, cn)

        def body(s):
            _, _, _, head_w = merged_head(s, cn)
            insvc = (s["so"] >= 0) & (s["sm"] >= 0)
            ownc = jnp.clip(s["so"], 0, B - 1)
            cand = insvc & (cn["wreq"][ownc] < head_w)
            rem = remaining_col(s, t)
            # victim: lexicographic min of (weight, -remaining, slot)
            k1 = jnp.where(cand, cn["wreq"][ownc], jnp.inf)
            c2 = cand & (k1 == jnp.min(k1))
            k2 = jnp.where(c2, -rem, jnp.inf)
            c3 = c2 & (k2 == jnp.min(k2))
            victim = jnp.argmax(c3)
            i = s["so"][victim]
            onehot_c = jnp.arange(C) == victim
            remw = rem[victim]
            s = dict(s)
            s["rpu"] = s["rpu"].at[i].set(s["su"][victim])
            s["rpm"] = s["rpm"].at[i].set(s["sm"][victim])
            s["rpok"] = s["rpok"].at[i].set(s["sok"][victim])
            s["rprm"] = s["rprm"].at[i].set(remw)
            s["rpec"] = s["rpec"].at[i].set(s["sec"][victim])
            s["rpdg"] = s["rpdg"].at[i].set(s["sdg"][victim])
            s["pre"] = s["pre"] + 1
            s["rpc"] = s["rpc"].at[i].add(1)
            s = sim_clear(s, onehot_c)
            s = release(s, onehot_c)
            return paused_insert(s, cn, i, cn["cls"][i])

        return lax.while_loop(cond, body, st)

    def phase_admit(st, cn, t):
        B = cn["arr"].shape[0]

        def cond(s):
            valid, _, _, _ = merged_head(s, cn)
            return s["sfree"].any() & valid

        def body(s):
            _, k_idx, i, _ = merged_head(s, cn)
            slot = jnp.argmax(s["sfree"])
            onehot_c = jnp.arange(C) == slot
            s = pop_head(s, cn, k_idx)
            s = dict(s)
            s["so"] = jnp.where(onehot_c, i, s["so"])
            s["sfree"] = s["sfree"] & ~onehot_c
            # fresh admission and paused resume, composed with masks
            # (each writes the union of the host branches' columns; the
            # non-taken branch writes the value the host left in place)
            if paused_on:
                isp = s["rpp"][i]
                # outage checkpoints carry stage model -1: restore the
                # realized prefix and budgets, then REPLAN instead of
                # resuming a calendar entry (host `resume`, pm < 0)
                isrp = (isp & (s["rpm"][i] < 0)) if cfg.fault_outages \
                    else jnp.asarray(False)
                isrs = isp & ~isrp
                s["su"] = jnp.where(onehot_c,
                                    jnp.where(isp, s["rpu"][i], 0), s["su"])
                s["sec"] = jnp.where(onehot_c,
                                     jnp.where(isp, s["rpec"][i], 0.0),
                                     s["sec"])
                s["sm"] = jnp.where(onehot_c & isrs, s["rpm"][i], s["sm"])
                s["sok"] = jnp.where(onehot_c & isp, s["rpok"][i], s["sok"])
                s["sdg"] = jnp.where(onehot_c,
                                     isp & s["rpdg"][i], s["sdg"])
            else:
                isp = jnp.asarray(False)
                isrp = isrs = jnp.asarray(False)
                s["su"] = jnp.where(onehot_c, 0, s["su"])
                s["sec"] = jnp.where(onehot_c, 0.0, s["sec"])
                s["sdg"] = jnp.where(onehot_c, False, s["sdg"])
            if cfg.deadline_sheds:
                t_d = cn["arr"][i] + cn["cap"][i]
                s["sddl"] = jnp.where(
                    onehot_c & jnp.isfinite(t_d) & (t_d > t),
                    t_d, s["sddl"])
            if paused_on:
                s["rpp"] = s["rpp"].at[i].set(False)
                # resume: restart the paused stage on the calendar with
                # the checkpointed remaining work (no replan); replan-on-
                # admit checkpoints skip the calendar entirely
                w = cn["wreq"][i]
                eng = cn["eom"][jnp.clip(s["rpm"][i], 0, M - 1)]
                s["je"] = jnp.where(onehot_c & isrs, eng, s["je"])
                if cfg.ps:
                    s["jrm"] = jnp.where(onehot_c & isrs,
                                         s["rprm"][i], s["jrm"])
                else:
                    s["jtc"] = jnp.where(onehot_c & isrs,
                                         t + s["rprm"][i], s["jtc"])
                    s["jwk"] = jnp.where(onehot_c & isrs,
                                         s["rprm"][i], s["jwk"])
                s["jw"] = jnp.where(onehot_c & isrs, w, s["jw"])
                s["wtd"] = s["wtd"] | (isrs & (w != 1.0))
                s["jsq"] = jnp.where(onehot_c & isrs, s["ns"], s["jsq"])
                s["ns"] = s["ns"] + jnp.where(isrs, 1, 0)
                s["res"] = s["res"] + jnp.where(isrs, 1, 0)
                s = lax.cond(isrs, lambda ss: peak_update(ss, cn),
                             lambda ss: ss, s)
            s["rad"] = jnp.where(isp, s["rad"],
                                 s["rad"].at[i].set(t))
            s["adm"] = s["adm"] + jnp.where(isp, 0, 1)
            s["snd"] = s["snd"] | (onehot_c & (~isp | isrp))
            return s

        return lax.while_loop(cond, body, st)

    def phase_replan_dispatch(st, cn, t):
        """Host steps 4-5b: ONE planner call over all capacity lanes,
        downgrade-lane override, vectorized dispatch, overload trim."""
        B = cn["arr"].shape[0]
        st = dict(st)
        st["rp"] = st["rp"] + 1
        ownc = jnp.clip(st["so"], 0, B - 1)
        el = t - cn["arr"][ownc]
        if cfg.priorities:
            el = el + cn["shift"][ownc]
        el32 = el.astype(jnp.float32)
        ec32 = st["sec"].astype(jnp.float32)
        delay_row = jnp.zeros(E, jnp.float32)
        if cfg.load_aware:
            act = st["je"] >= 0
            park = jnp.where(act, jnp.clip(st["je"], 0, E - 1), E)
            if cfg.tokens:
                with jax.named_scope(CALENDAR_SCOPE):
                    # TokenWorkModel.delays over the live sequence COUNT (the
                    # KV/batch physics depends on how many sequences share the
                    # decode step, never on priority weights); slowdown mirror
                    # of EngineTokenModel.slowdown with the same barriers as
                    # traced_token_rates so host == compiled bitwise
                    occw = jnp.zeros(E + 1, st["sec"].dtype).at[park].add(
                        jnp.where(act, 1.0, 0.0))[:E]
                    n = occw + 1.0
                    b = jnp.minimum(n, cn["tkc"])
                    prod = lax.optimization_barrier(cn["tkv"] * b)
                    sb = jnp.maximum(cn["tkw"] + prod, cn["tkf"] * b)
                    q1 = lax.optimization_barrier(n / b)
                    q2 = lax.optimization_barrier(sb / cn["tk1"])
                    sd = lax.optimization_barrier(q1 * q2)
                    dr64 = (sd - 1.0) * cn["ms"]
                    # the host casts the dict values into a float32 row first
                    delay_row = jnp.where(cn["hasm"], dr64,
                                          0.0).astype(jnp.float32)
            elif cfg.ps:
                # FleetLoadModel.delays over the live (weighted) occupancy
                occw = jnp.zeros(E + 1, st["sec"].dtype).at[park].add(
                    jnp.where(act,
                              st["jw"] if cfg.priorities else 1.0, 0.0))[:E]
                dr64 = (jnp.maximum(1.0, (occw + 1.0) / cn["conc"]) - 1.0) \
                    * cn["ms"]
                # the host casts the dict values into a float32 row first
                delay_row = jnp.where(cn["hasm"], dr64,
                                      0.0).astype(jnp.float32)
            if pol.wants_forecast and pol.backlog_delay > 0.0:
                # backlog-drain anchor (PredictiveGate.forecast_delay_row):
                # max against the float32 row in float64, like the host
                if cfg.ps:
                    rem = jnp.where(act, jnp.maximum(st["jrm"], 0.0), 0.0)
                    jr = jnp.where(act, job_rates(st, cn), 0.0)
                else:
                    rem = jnp.where(act,
                                    jnp.maximum(st["jtc"] - t, 0.0), 0.0)
                    jr = jnp.where(act, 1.0, 0.0)
                backlog = jnp.zeros(E + 1, rem.dtype).at[park].add(rem)[:E]
                rate = jnp.zeros(E + 1, rem.dtype).at[park].add(jr)[:E]
                drain = jnp.where(rate > 0, backlog / rate, 0.0)
                delay_row = jnp.maximum(
                    delay_row.astype(st["sec"].dtype),
                    pol.backlog_delay * drain).astype(jnp.float32)
        if cfg.fault_outages:
            # blocked-depth column from the live availability mask: the
            # planner admits target v iff bd[v] <= depth[u], i.e. every
            # stage strictly past the realized node runs on an up engine
            # (host blocked_depth_table, recomputed per fault transition;
            # here recomputed in-trace each replan — the mask is a traced
            # operand, so outages cause ZERO new planner programs)
            pmn = cn["td"].path_models
            deadp = (pmn >= 0) & ~st["av"][
                cn["eom"][jnp.clip(pmn, 0, M - 1)]]
            posn = jnp.arange(pmn.shape[1])[None, :]
            bd = jnp.max(jnp.where(deadp, posn + 1, 0),
                         axis=1, initial=0).astype(jnp.float32)
        else:
            bd = None
        need = st["snd"]
        # the round's width-1 sweeps, counted on the replicated need mask
        # (each needy lane is swept once, by the one device owning it, and
        # a downgraded lane once more, for its min-cost plan)
        sweeps = jnp.sum(jnp.where(need, 1, 0))
        if pol.max_occupancy is not None and pol.downgrade:
            sweeps = sweeps + jnp.sum(jnp.where(need & st["sdg"], 1, 0))
        st["swp"] = st["swp"] + sweeps
        if cfg.n_shards > 1:
            # Sharded control plane: every device keeps the full replicated
            # bookkeeping (the event loop is sequential and globally
            # coupled), but the expensive part of a replan round — the
            # per-lane trie sweeps below — is partitioned by residue class
            # ``lane % n_shards == axis_index``.  Each device plans only
            # its own needy lanes; the one `psum` after the sweep is the
            # ONLY cross-device collective per replan round and carries the
            # planned (target, next-model) pair back to every device.
            # Lane-independence of the planner (see the sweep comment
            # below) makes the merged result bit-identical to the
            # single-device sweep.
            mine = need & ((jnp.arange(C) % cfg.n_shards)
                           == lax.axis_index(LANE_AXIS))
        else:
            mine = need

        # Plan ONLY the lanes that need dispatch, one width-1 kernel sweep
        # per lane: the planner's math is lane-independent (per-request
        # lexicographic minima, the same node whatever the tiling), so the
        # single-lane call is bit-identical to that lane of a
        # capacity-wide call — but a steady-state event has 1-2 needy
        # lanes, so this trades C full-trie sweeps for n_needed and is
        # what makes the engine trie-size-robust (the batched form was
        # ~C x slower per event on the 5461-node MathQA trie).  The fused
        # sweep is one node tile over the whole trie, with no loop inside.
        # Downgraded lanes pick the min-cost scalar bundle per lane
        # instead of a second capacity-wide sweep (the host uses a float64
        # search; divergence is possible at float32 resolution and
        # documented in EVENT_ENGINE.md).
        def plan_lane(c):
            tgt, nxt, done = c
            i = jnp.argmax(mine & ~done)
            pre1 = lax.dynamic_slice_in_dim(st["su"], i, 1)
            el1 = lax.dynamic_slice_in_dim(el32, i, 1)
            ec1 = lax.dynamic_slice_in_dim(ec32, i, 1)
            t1, n1 = traced_fleet_plan(cn["td"], pre1, el1, ec1,
                                       delay_row, cn["sc"],
                                       kind=cfg.kind, variant=cfg.variant,
                                       blocked=bd)
            if pol.max_occupancy is not None and pol.downgrade:
                dg1 = lax.dynamic_slice_in_dim(st["sdg"], i, 1)[0]
                t1, n1 = lax.cond(
                    dg1,
                    lambda a: traced_fleet_plan(cn["td"], *a, cn["scdg"],
                                                kind=cfg.kind_dg,
                                                variant=cfg.variant),
                    lambda a: (t1, n1), (pre1, el1, ec1, delay_row))
            tgt = lax.dynamic_update_slice_in_dim(tgt, t1, i, 0)
            nxt = lax.dynamic_update_slice_in_dim(nxt, n1, i, 0)
            return tgt, nxt, done.at[i].set(True)

        tgt, nxt, _ = lax.while_loop(
            lambda c: (mine & ~c[2]).any(), plan_lane,
            (jnp.full(C, -1, i32), jnp.full(C, -1, i32),
             jnp.zeros(C, bool)))
        if cfg.n_shards > 1:
            # the one collective per replan round: lanes are shifted +1 so
            # an owner's infeasible plan (-1) and a non-owner's zero both
            # decode to -1 after the sum (each needy lane has exactly one
            # owner, so the sum IS the owner's value)
            enc = lax.psum(jnp.stack([jnp.where(mine, tgt + 1, 0),
                                      jnp.where(mine, nxt + 1, 0)]),
                           LANE_AXIS)
            tgt = jnp.where(need, enc[0] - 1, -1)
            nxt = jnp.where(need, enc[1] - 1, -1)
        if cfg.explore:
            # exploration lane (host 4c): a pre-drawn request's FIRST
            # dispatch (root prefix) overrides the planner's pick with
            # its explore model, iff the float32 budget guard passes
            # against the live annotation version.  Same op order as the
            # host guard (subtract, add, compare — all exact IEEE f32),
            # applied after the downgrade lane, elementwise on replicated
            # values (no collective).
            xm = cn["xpm"][ownc]
            xv = cn["child"][0, jnp.clip(xm, 0, M - 1)]
            xvc = jnp.clip(xv, 0, cn["td"].lat.shape[0] - 1)
            ok = (need & (nxt >= 0) & (st["su"] == 0) & (xm >= 0)
                  & (el32 + (cn["td"].lat[xvc] - cn["td"].lat[0])
                     <= cn["sc"][2])
                  & (ec32 + (cn["td"].cost[xvc] - cn["td"].cost[0])
                     <= cn["sc"][1]))
            if cfg.fault_outages:
                # host 4c skips the explore override when the explore
                # model's engine is down
                ok = ok & st["av"][cn["eom"][jnp.clip(xm, 0, M - 1)]]
            nxt = jnp.where(ok, xm, nxt)
            st["xpc"] = st["xpc"] + jnp.sum(jnp.where(ok, 1, 0))
        stop = need & (nxt < 0)
        infeas = stop & (tgt < 0)
        oc = jnp.full(C, _OC_SERVED, i32)
        if pol.gates:
            started = cn["depth"][st["su"]] > 0
            shed_m = infeas & started
            rej_m = infeas & ~started
            if fault_any:
                # a fault-touched request that becomes infeasible is a
                # FAILURE, not a shed/reject (host classify conversion)
                flt = st["rfl"][ownc]
                fail_m = infeas & flt
                shed_m = shed_m & ~flt
                rej_m = rej_m & ~flt
                oc = jnp.where(fail_m, _OC_FAILED, oc)
                st["ffc"] = st["ffc"] + jnp.sum(jnp.where(fail_m, 1, 0))
            oc = jnp.where(shed_m, _OC_SHED, oc)
            oc = jnp.where(rej_m, _OC_REJECTED, oc)
            st["shd"] = st["shd"] + jnp.sum(jnp.where(shed_m, 1, 0))
            n_rej = jnp.sum(jnp.where(rej_m, 1, 0))
            st["rej"] = st["rej"] + n_rej
            st["adm"] = st["adm"] - n_rej
        st = record_terminal(st, cn, st["so"], stop, t, oc, st["sec"])
        start_m = need & (nxt >= 0)
        if cfg.fault_failures:
            # seeded stage-failure draws, indexed per (request, depth,
            # attempt) and consulted BEFORE the executor charges cost
            # (host dispatch gate).  A drawn failure bumps the attempt
            # counter; exhaustion fails the request terminally, otherwise
            # the slot is held for t + backoff(attempt) and replanned.
            mr = cfg.max_retries
            d0 = cn["depth"][st["su"]]
            d0c = jnp.clip(d0, 0, cfg.max_depth - 1)
            a0 = st["rpat"][ownc, d0c]
            draw = start_m & cn["fdr"][ownc, d0c,
                                       jnp.clip(a0, 0, mr)]
            scat = jnp.where(draw, ownc, B)
            st["rpat"] = st["rpat"].at[scat, d0c].add(1, mode="drop")
            st["rfl"] = st["rfl"].at[scat].set(True, mode="drop")
            a1 = a0 + 1
            exh = draw & (a1 > mr)
            retry = draw & ~exh
            st["fsc"] = st["fsc"] + jnp.sum(jnp.where(draw, 1, 0))
            st["frt"] = st["frt"] + jnp.sum(jnp.where(retry, 1, 0))
            st["ffc"] = st["ffc"] + jnp.sum(jnp.where(exh, 1, 0))
            nb = cn["fbo"].shape[0]
            st["srt"] = jnp.where(
                retry, t + cn["fbo"][jnp.clip(a0, 0, nb - 1)], st["srt"])
            st = record_terminal(st, cn, st["so"], exh, t,
                                 jnp.full(C, _OC_FAILED, i32), st["sec"])
            st = release(st, exh)
            start_m = start_m & ~draw
        d = cn["depth"][st["su"]]
        row = cn["row"][ownc]
        nxtc = jnp.clip(nxt, 0, M - 1)
        sres = cn["tabs"][row, d, nxtc]
        c = cn["tabc"][row, d, nxtc]
        lat = cn["tabl"][row, d, nxtc]
        st["sec"] = jnp.where(start_m, st["sec"] + c, st["sec"])
        st["sm"] = jnp.where(start_m, nxt, st["sm"])
        st["sok"] = jnp.where(start_m, sres, st["sok"])
        # calendar starts, seq assigned in ascending slot order; the scan
        # runs in int32: the TPU emulates a 64-bit one in scoped VMEM,
        # which a few hundred slots overflow
        rank = jnp.cumsum(jnp.where(start_m, 1, 0), dtype=i32) - 1
        st["jsq"] = jnp.where(start_m, st["ns"] + rank, st["jsq"])
        st["ns"] = st["ns"] + jnp.sum(jnp.where(start_m, 1, 0))
        st["je"] = jnp.where(start_m, cn["eom"][nxtc], st["je"])
        if cfg.ps:
            st["jrm"] = jnp.where(start_m, lat, st["jrm"])
        else:
            st["jtc"] = jnp.where(start_m, t + lat, st["jtc"])
            st["jwk"] = jnp.where(start_m, lat, st["jwk"])
        if cfg.priorities:
            w = cn["wreq"][ownc]
            st["jw"] = jnp.where(start_m, w, st["jw"])
            st["wtd"] = st["wtd"] | (start_m & (w != 1.0)).any()
        st = release(st, stop)
        st = peak_update(st, cn)
        st["snd"] = jnp.zeros(C, bool)
        if pol.max_occupancy is not None:
            st = phase_overload(st, cn, t)
        return st

    def phase_overload(st, cn, t):
        """Host 5b: per engine over its occupancy target, iteratively trim
        the lowest goodput-per-token jobs (downgrade first, shed when
        already downgraded) — CostAwareShed.overload_actions."""
        maxo = pol.max_occupancy
        for e in range(E):
            def on_engine(s):
                insvc = (s["so"] >= 0) & (s["sm"] >= 0)
                return insvc & (cn["eom"][jnp.clip(s["sm"], 0, M - 1)] == e)

            n0 = jnp.sum(jnp.where(on_engine(st), 1, 0))
            excess = n0 - maxo

            def cond(c):
                s, taken, cnt = c
                return cnt < excess

            def body(c):
                s, taken, cnt = c
                cand = on_engine(s) & ~taken
                acc = cn["bacc"][s["su"]]
                remc = jnp.maximum(cn["mcost"][s["su"]] - s["sec"], 0.0)
                score = jnp.where(
                    jnp.isfinite(acc),
                    jnp.maximum(acc, 0.0) / (s["sec"] + remc + 1e-9),
                    -jnp.inf)
                key = jnp.where(cand, score, jnp.inf)
                pick = cand & (key == jnp.min(key))
                victim = jnp.argmax(pick)
                onehot_c = jnp.arange(C) == victim
                dg = pol.downgrade & ~s["sdg"][victim]
                s = dict(s)
                s["sdg"] = jnp.where(onehot_c & dg, True, s["sdg"])
                s["dgc"] = s["dgc"] + jnp.where(dg, 1, 0)
                shed_m = onehot_c & ~dg
                s = record_terminal(s, cn, s["so"], shed_m, t,
                                    jnp.full(C, _OC_SHED, i32), s["sec"])
                s["shd"] = s["shd"] + jnp.where(dg, 0, 1)
                s = sim_clear(s, shed_m)
                s = release(s, shed_m)
                return s, taken | onehot_c, cnt + 1

            st, _, _ = lax.while_loop(
                cond, body, (st, jnp.zeros(C, bool), jnp.asarray(0, "int64")))
        return st

    def next_event_time(st, cn):
        B = cn["arr"].shape[0]
        t_arr = jnp.where(st["ap"] < B,
                          cn["arrs"][jnp.clip(st["ap"], 0, B - 1)], jnp.inf)
        tn = jnp.minimum(t_arr, next_completion(st, cn))
        tn = jnp.minimum(tn, jnp.min(st["sddl"]))
        if cfg.fault_outages:
            F = cn["ftt"].shape[0] - 1
            tn = jnp.minimum(tn, cn["ftt"][jnp.clip(st["fi"], 0, F)])
        if cfg.fault_failures:
            tn = jnp.minimum(tn, jnp.min(st["srt"]))
        if paused_on and cfg.deadline_sheds:
            req = jnp.clip(st["pb"], 0, B - 1)
            activep = jnp.arange(P)[None, :] < st["pn"][:, None]
            pddl = jnp.where(activep,
                             cn["arr"][req] + cn["cap"][req], jnp.inf)
            tn = jnp.minimum(tn, jnp.min(pddl))
        return tn

    def event_body(st, cn):
        # runs in the clock's scope (see `step`); the admission and
        # dispatch phases open their own
        t = st["tn"]
        st = {**st, "ev": st["ev"] + 1, "snd": jnp.zeros(C, bool)}
        if cfg.tokens:
            act = st["je"] >= 0
            with jax.named_scope(CALENDAR_SCOPE):
                st = calendar_seconds(st, cn, t, act)
                jrm, tl = traced_advance(
                    st["jrm"], st["tl"], t, st["je"], st["jw"], act,
                    cn["conc"], st["wtd"],
                    tok=(cn["tkw"], cn["tkv"], cn["tkf"], cn["tkc"],
                         cn["tk1"]))
            st = {**st, "jrm": jrm, "tl": tl}
        elif cfg.ps:
            act = st["je"] >= 0
            jrm, tl = traced_advance(st["jrm"], st["tl"], t, st["je"],
                                     st["jw"], act, cn["conc"], st["wtd"])
            st = {**st, "jrm": jrm, "tl": tl}
        st = phase_completions(st, cn, t)
        if cfg.fault_outages:
            with jax.named_scope(_ADMIT):
                st = phase_faults(st, cn, t)
        st = phase_deadline_sheds(st, cn, t)
        with jax.named_scope(_ADMIT):
            st = phase_arrivals(st, cn, t)
            st = phase_queue_rejections(st, cn, t)
            if cfg.fault_failures:
                st = phase_retry_release(st, cn, t)

        # 3-5 cycle: preempt -> admit/resume -> replan -> dispatch,
        # repeated while freed slots can absorb queued arrivals
        def cyc_cond(c):
            st_, go = c
            return go

        def cyc_body(c):
            s, _ = c
            s = phase_preempt(s, cn, t)
            s = phase_admit(s, cn, t)
            need_any = s["snd"].any()
            with jax.named_scope(_DISPATCH):
                s = lax.cond(need_any,
                             lambda ss: phase_replan_dispatch(ss, cn, t),
                             lambda ss: ss, s)
            valid, _, _, _ = merged_head(s, cn)
            again = jnp.where(
                need_any,
                (s["sfree"].any() & valid) | any_preemptable(s, cn),
                any_preemptable(s, cn))
            return s, again

        # the cycle's loop is admission's: its sequencing counts there
        with jax.named_scope(_ADMIT):
            st, _ = lax.while_loop(cyc_cond, cyc_body,
                                   (st, jnp.asarray(True)))
        return {**st, "tn": next_event_time(st, cn)}

    def step(st, cn, t_hi):
        def cond(s):
            return jnp.isfinite(s["tn"]) & (s["tn"] <= t_hi)

        # the event loop is the virtual clock's: its sequencing (the gaps
        # between the body's operations, the loop-carried copies) counts
        # in the clock's scope, the phases that open their own aside
        with jax.named_scope(_CLOCK):
            return lax.while_loop(cond, lambda s: event_body(s, cn), st)

    if cfg.n_shards > 1:
        # SPMD wrapper: every operand and result is REPLICATED (empty
        # PartitionSpec) — the sequential event loop's bookkeeping must be
        # identical on every device so the outer while_loops take the same
        # trip counts everywhere (a collective inside a device-varying
        # loop would deadlock).  What the mesh buys is the replan sweep:
        # each device walks only its residue class of needy lanes
        # (collective-free inner while_loop — device-varying trip counts
        # are legal there), and one psum per replan round rebroadcasts the
        # merged plans.  check_vma=False because jax cannot prove the
        # psum output replicated through the surrounding loops.
        from jax.sharding import PartitionSpec as PSpec

        from repro.dist.sharding import lane_mesh
        rep = PSpec()
        step = jax.shard_map(step, mesh=lane_mesh(cfg.n_shards),
                             in_specs=(rep, rep, rep), out_specs=rep,
                             check_vma=False)
    jitted = jax.jit(step, donate_argnums=(0,))
    _ENGINE_CACHE[cfg] = jitted
    return jitted


def _tabulate_executor(executor: StageExecutor, requests: np.ndarray,
                       probe: np.ndarray, t_start: float,
                       work_model=None, engines=None,
                       engine_of_model=None):
    """Evaluate the executor over (unique request value, depth, model)
    once, producing the dense (U, D, M) tables the traced dispatch
    gathers from.  This is what makes executors compilable — and why the
    compiled engine requires them to be pure functions of that triple
    (the host loop passes the live event time; here every cell is probed
    at ``t_start``).  ``probe`` is a (D, M) bool mask of the (depth,
    model) pairs the trie can actually dispatch — only those cells are
    evaluated, so executors (like the oracle's) that index stage tables
    by depth never see out-of-range probes; unreachable cells stay at
    benign zeros and are masked out of every traced use.

    Under a ``work_model`` (token calendar, ISSUE 10) the latency cell is
    the stage's token footprint in batch-1 seconds — the same host-side
    `TokenWorkModel.work_of` the host loop calls at dispatch, so the two
    calendars start from bit-identical work quanta; the requirement that
    ``stage_tokens`` be a pure function of (request, depth, model) is what
    makes the tabulation valid."""
    uniq, row = np.unique(requests, return_inverse=True)
    U = uniq.shape[0]
    D, M = probe.shape
    tab_s = np.zeros((U, D, M), dtype=bool)
    tab_c = np.zeros((U, D, M), dtype=np.float64)
    tab_l = np.zeros((U, D, M), dtype=np.float64)
    for ui, rv in enumerate(uniq):
        for d, m in zip(*np.nonzero(probe)):
            s, c, lat = executor(int(rv), int(d), int(m), t_start)
            if work_model is not None:
                ptok, dtok = work_model.stage_tokens(int(rv), int(d),
                                                     int(m))
                lat = work_model.work_of(
                    engines[int(engine_of_model[int(m)])], ptok, dtok)
            tab_s[ui, d, m] = bool(s)
            tab_c[ui, d, m] = float(c)
            tab_l[ui, d, m] = float(lat)
    return tab_s, tab_c, tab_l, row.astype(np.int32)


def run_events_compiled(
    trie: Trie,
    ann: TrieAnnotations,
    obj: Objective,
    requests: np.ndarray,
    executor: StageExecutor,
    *,
    arrivals: np.ndarray | None = None,
    capacity: int | None = None,
    policy: str = "dynamic",
    admission=None,
    classes: np.ndarray | None = None,
    class_specs=None,
    preempt: bool = True,
    restrict_nodes: np.ndarray | None = None,
    load_probe=None,
    fleet_load=None,
    work_model=None,
    t_start: float = 0.0,
    plan_variant: str | None = None,
    annotation_schedule=None,
    refresh=None,
    explore=None,
    faults=None,
    epoch: int = DEFAULT_EPOCH,
    stream: bool = False,
    devices: int | None = None,
) -> tuple[list[ExecutionResult], EventStats]:
    """Compiled twin of `repro.core.events.run_events` (same signature
    plus ``epoch``/``stream``/``devices``); see that function for the
    serving semantics — the two are bit-compatible on the differential
    oracle.

    ``epoch`` sets how many arrivals each jitted step ingests before the
    host drains progress scalars (a throughput/latency knob; any value
    gives identical results and hits the same compiled program).  With
    ``stream=True`` the per-request result list is NOT materialized:
    the call returns ``(summary_dict, EventStats)`` where the summary
    carries the streaming Welford moments, quantile histogram and
    counters — constant host memory regardless of trace length (the
    1M-request replay path, `benchmarks/trace_replay.py`).

    ``devices`` shards the control plane's replan sweeps over a 1-D lane
    mesh (`repro.dist.sharding.lane_mesh`): each device plans only the
    needy lanes in its residue class and one `psum` per replan round
    merges the plans — bit-identical dispositions and summaries at any
    device count (docs/EVENT_ENGINE.md, "Sharding").  ``None``/``1``
    keeps the single-device program unchanged.  On CPU hosts virtual
    devices come from ``--xla_force_host_platform_device_count``.

    ``annotation_schedule`` swaps in re-annotated `TrieDevice` versions
    mid-run (ISSUE 8): the epoch loop splits at each swap time, so every
    event at ``t <= t_swap`` runs under the old annotations and the swap
    is a pure operand substitution — the annotation columns are traced
    operands, ZERO new compiled programs per swap.  ``explore`` enables
    the same epsilon-greedy exploration lane as the host loop
    (bit-compatible float32 budget guard).  ``refresh`` (the online
    posterior loop) needs host-side service observations and raises
    `NotImplementedError` here — use ``compiled=False`` or a precomputed
    ``annotation_schedule``.

    ``faults`` takes the same `repro.core.faults.FaultSchedule` as the
    host loop and is bit-compatible with it on the chaos differential:
    outage transitions become traced (time, engine, up) operand columns
    whose availability mask feeds the planner's blocked-depth operand
    (ZERO new compiled programs per outage), victims checkpoint into the
    paused buffer as replan-on-admit entries, and seeded stage-failure
    draws gate dispatch with capped exponential backoff.  Unsupported
    here (use the host loop): ``timeout_k`` (needs host-side latency
    forecasts), ``recovery="restart"``, and combining faults with
    forecast/occupancy admission policies.

    ``work_model`` (ISSUE 10) switches the engine calendar to the
    token-level model, bit-compatible with the host loop: stage work is
    tabulated host-side as the (prefill, decode) token footprint in
    batch-1 seconds via `TokenWorkModel.work_of`, and the traced drain
    uses the continuous-batching decode-step rate curve
    (`traced_token_rates`) whose coefficients ride as (E,) operands —
    new token models or curve parameters compile ZERO new programs.
    Requires concrete `TokenWorkModel`/`EngineTokenModel` instances and
    is mutually exclusive with ``fleet_load``/``load_probe``.
    """
    if policy not in ("dynamic", "dynamic_load_aware"):
        raise ValueError(f"unsupported events policy {policy!r}: the static "
                         "baseline plans once per request — use run_cohort's "
                         "scalar path")
    if load_probe is not None:
        raise NotImplementedError(
            "compiled event engine cannot trace a host load_probe callback; "
            "use fleet_load=FleetLoadModel(...) or the host loop")
    if work_model is not None:
        if fleet_load is not None:
            raise ValueError("work_model and fleet_load are mutually "
                             "exclusive: the token calendar replaces the "
                             "scalar slowdown model")
        if getattr(work_model, "stage_tokens", None) is None:
            raise ValueError("work_model.stage_tokens must be set: the "
                             "token calendar needs per-stage "
                             "(prefill, decode) token counts")
        # like fleet_load: the traced calendar needs the concrete
        # decode-step coefficients, not a duck-typed work model
        from repro.serving.loadsim import EngineTokenModel, TokenWorkModel
        if not isinstance(work_model, TokenWorkModel) or not all(
                isinstance(m, EngineTokenModel)
                for m in work_model.engines.values()):
            raise NotImplementedError(
                "compiled event engine supports TokenWorkModel with "
                "EngineTokenModel entries; use the host loop for duck-typed "
                "work models")
    if refresh is not None:
        raise NotImplementedError(
            "compiled event engine cannot run the online estimator refresh "
            "(posterior updates are host-side observations); use the host "
            "loop (compiled=False) or a precomputed annotation_schedule")
    pol = get_policy(admission)
    tpol = traced_admission(pol)  # raises for custom policy subclasses
    fault_outages = faults is not None and bool(faults.outages)
    fault_failures = faults is not None and (
        faults.stage_failure_rate > 0.0 or faults.failure_table is not None)
    if faults is not None:
        if faults.timeout_k is not None:
            raise NotImplementedError(
                "compiled event engine cannot trace the stage-timeout model "
                "(timeout_k needs the host loop's live latency forecasts); "
                "use compiled=False")
        if faults.recovery != "checkpoint":
            raise NotImplementedError(
                f"compiled event engine only supports recovery='checkpoint' "
                f"(got {faults.recovery!r}); restart-from-root is a host-loop "
                "baseline for benchmarks/chaos.py")
        if (fault_outages or fault_failures) and (
                pol.wants_forecast or pol.max_occupancy is not None):
            raise NotImplementedError(
                "compiled event engine does not combine fault injection with "
                "forecast- or occupancy-gated admission policies; use the "
                "host loop (compiled=False)")
    requests = np.asarray(requests)
    B = int(requests.shape[0])
    host_s: dict[str, float] = {}
    with _host_span(host_s, "build", requests=B) as span:
        if arrivals is None:
            arrivals = np.zeros(B, dtype=np.float64)
        else:
            arrivals = np.asarray(arrivals, dtype=np.float64)
            if arrivals.shape != (B,):
                raise ValueError(f"arrivals shape {arrivals.shape} != ({B},)")
            if B and (not np.all(np.isfinite(arrivals)) or arrivals.min() < 0):
                raise ValueError("arrivals must be finite and non-negative")
        if capacity is None:
            capacity = B if arrivals.size == 0 or arrivals.max() == 0.0 \
                else min(B, _DEFAULT_CAPACITY)
        C = int(capacity)
        if B and C < 1:
            raise ValueError("capacity must be >= 1")

        priorities = class_specs is not None
        if not priorities and classes is not None:
            raise ValueError("classes requires class_specs (the SLOClass table "
                             "the indices point into)")
        base_cap = obj.lat_cap if obj.lat_cap is not None else np.inf
        if priorities:
            specs = tuple(class_specs)
            if not specs:
                raise ValueError("class_specs must be a non-empty sequence of "
                                 "SLO classes")
            cls_idx = (np.zeros(B, dtype=np.int64) if classes is None
                       else np.asarray(classes, dtype=np.int64))
            if cls_idx.shape != (B,):
                raise ValueError(f"classes shape {cls_idx.shape} != ({B},)")
            if B and (cls_idx.min() < 0 or cls_idx.max() >= len(specs)):
                raise ValueError(
                    f"classes must index the {len(specs)} class_specs entries")
            cap_cls = np.array([c.deadline_s if c.deadline_s is not None
                                else base_cap for c in specs], dtype=np.float64)
            w_cls = np.array([c.weight for c in specs], dtype=np.float64)
            cap_req = cap_cls[cls_idx]
            weight_req = w_cls[cls_idx]
            K = len(specs)
        else:
            cls_idx = np.zeros(B, dtype=np.int64)
            cap_req = np.full(B, base_cap)
            weight_req = np.ones(B)
            w_cls = np.ones(1)
            K = 1

        stats = EventStats(capacity=C, policy=pol.name,
                           outcome=[SERVED] * B,
                           arrival_t=arrivals.copy(),
                           admit_t=np.zeros(B, dtype=np.float64),
                           done_t=np.zeros(B, dtype=np.float64),
                           class_of=cls_idx.copy() if priorities else None,
                           preempt_count=np.zeros(B, dtype=np.int64),
                           host_s=host_s)
        if B == 0:
            return ([], stats) if not stream else (
                _empty_summary(stats), stats)

        td = TrieDevice.build(trie, ann, restrict_nodes)
        swaps: list[tuple[float, TrieDevice]] = []
        if annotation_schedule:
            sched = sorted(annotation_schedule, key=lambda sa: float(sa[0]))
            for i, (ts, swap_ann) in enumerate(sched):
                ts = float(ts)
                if not np.isfinite(ts) or ts < 0:
                    raise ValueError(
                        f"annotation_schedule swap time {ts!r} must be finite "
                        "and non-negative")
                swap_td = TrieDevice.build(trie, swap_ann, restrict_nodes)
                swap_td.version = i + 1
                swaps.append((ts, swap_td))
        lat_shift = np.zeros(B)
        eff_cap = None
        if priorities:
            finite = cap_req[np.isfinite(cap_req)]
            eff_cap = float(finite.max()) if finite.size else None
            if eff_cap is not None:
                lat_shift = np.where(np.isfinite(cap_req),
                                     eff_cap - cap_req, -np.inf)
                # same float32 elapsed-shift resolution caveat as the host
                # loop (see run_events): warn when the deadline spread makes
                # the quantization material for the tightest class
                step = float(np.spacing(np.float32(eff_cap)))
                if step > 1e-3 * float(finite.min()):
                    warnings.warn(
                        f"class deadline spread ({finite.min():.3g}s .. "
                        f"{eff_cap:.3g}s) exceeds float32 elapsed-shift "
                        f"resolution ({step:.3g}s at the largest cap): the "
                        "planner's feasibility may lag the deadline "
                        "bookkeeping by up to that much for tight classes",
                        stacklevel=2)
        plan_obj = obj if eff_cap is None \
            else dataclasses.replace(obj, lat_cap=eff_cap)
        engines = trie_engines(trie.template)
        E = len(engines)
        M = trie.template.n_models
        max_depth = trie.template.max_depth
        load_aware = policy == "dynamic_load_aware"

        term_mask = trie.terminal.copy()
        if restrict_nodes is not None:
            keep = np.zeros(trie.n_nodes, dtype=bool)
            keep[restrict_nodes] = True
            term_mask &= keep
        pol.bind(trie, ann, obj, term_mask)
        tpol = traced_admission(pol)  # re-distill with bound min_path_lat
        explore_model = _explore_tables(trie, term_mask, B, explore)
        deadline_sheds = pol.shed_on_deadline and bool(
            np.isfinite(cap_req).any())

        # load coupling: the traced calendar needs the concrete
        # EngineLoadModel parameters, not a duck-typed slowdown callable
        conc = np.full(E, np.inf)
        ms = np.ones(E)
        hasm = np.zeros(E, dtype=bool)
        tokens = work_model is not None
        ps = tokens or (load_aware and fleet_load is not None)
        if tokens:
            # token calendar: the decode-step curve coefficients
            # become (E,) traced operands; conc stays inf (shape source only
            # — the rate curve never reads it).  tk1 = decode_step_s(1) is
            # precomputed here so the trace and the host share one rounding.
            tkw = np.zeros(E)
            tkv = np.zeros(E)
            tkf = np.zeros(E)
            tkc = np.ones(E)
            tk1 = np.ones(E)
            for j, e in enumerate(engines):
                m = work_model.engines.get(e)
                if m is None:
                    raise ValueError(
                        f"work_model has no token model for engine {e!r}: the "
                        "token calendar needs every trie engine's decode curve")
                tkw[j] = float(m.t_weights_s)
                tkv[j] = float(m.t_kv_s)
                tkf[j] = float(m.t_flop_s)
                tkc[j] = float(m.kv_capacity)
                tk1[j] = max(float(m.t_weights_s) + float(m.t_kv_s),
                             float(m.t_flop_s))
                ms[j] = float(work_model.mean_service_s.get(e, 1.0))
                hasm[j] = True
        elif ps:
            from repro.serving.loadsim import EngineLoadModel, FleetLoadModel
            if not isinstance(fleet_load, FleetLoadModel) or not all(
                    isinstance(m, EngineLoadModel)
                    for m in fleet_load.engines.values()):
                raise NotImplementedError(
                    "compiled event engine supports FleetLoadModel with "
                    "EngineLoadModel entries; use the host loop for duck-typed "
                    "load models")
            for j, e in enumerate(engines):
                m = fleet_load.engines.get(e)
                if m is not None:
                    conc[j] = float(m.concurrency)
                    ms[j] = float(fleet_load.mean_service_s.get(e, 1.0))
                    hasm[j] = True

        order = np.argsort(arrivals, kind="stable")
        seq_of = np.empty(B, dtype=np.int64)
        seq_of[order] = np.arange(B)
        members = np.full((K, B), -1, dtype=np.int32)
        cls_ord = cls_idx[order].astype(np.int32)
        for k in range(K):
            mem_k = order[cls_ord == k]
            members[k, :mem_k.size] = mem_k

        # only (depth, model) pairs some trie node can dispatch get probed
        probe = np.zeros((max_depth + 1, M), dtype=bool)
        node_depth = trie.depth.astype(np.int64)
        has_child = trie.child >= 0  # (n_nodes, M)
        np.logical_or.at(probe, node_depth, has_child)
        best_acc, min_cost = _subtree_reductions(trie, ann, term_mask)

        n_shards = 1 if devices is None else int(devices)
        if n_shards < 1:
            raise ValueError(f"devices must be >= 1, got {devices}")
        if n_shards > 1:
            from repro.dist.sharding import lane_mesh
            lane_mesh(n_shards)  # availability check: clear error + CPU recipe

        sketch = QuantileSketch.log_spaced()
        cfg = _EngineConfig(
            capacity=C, n_classes=K, n_engines=E, n_models=M,
            max_depth=max_depth, priorities=priorities, preempt=bool(preempt),
            ps=ps, load_aware=load_aware, tokens=tokens,
            deadline_sheds=deadline_sheds,
            pol=tpol, kind=obj.kind, kind_dg="min_cost",
            variant=_resolve_variant(plan_variant), n_bins=sketch.n_bins,
            n_shards=n_shards, explore=explore_model is not None,
            fault_outages=fault_outages, fault_failures=fault_failures,
            max_retries=int(faults.max_retries) if faults is not None else 0,
            # outage victims can stack past C across repeated outages, so the
            # paused buffer is sized B under fault injection (shapes already
            # carry B-sized columns — no retrace cost)
            paused_cap=B if fault_outages else (C if priorities else 0))
        step = _build_step(cfg)
        span.set_metadata(nodes=int(trie.n_nodes),
                          dmax=int(td.path_models.shape[1]),
                          models=M, engines=E,
                          plan_tiles=trie_plan_tiles(int(trie.n_nodes),
                                                     cfg.variant))
    with _host_span(host_s, "tabulate", requests=B):
        tab_s, tab_c, tab_l, row = _tabulate_executor(
            executor, requests, probe, t_start, work_model=work_model,
            engines=engines,
            engine_of_model=np.asarray(td.engine_of_model, dtype=np.int64))

    import jax
    import jax.numpy as jnp

    with jax.enable_x64(True):
        with _host_span(host_s, "upload", requests=B):
            dg_obj = Objective("min_cost", acc_floor=-1.0,
                               cost_cap=obj.cost_cap, lat_cap=plan_obj.lat_cap)
            cn = {
                "td": td,
                "sc": objective_scalars(plan_obj),
                "scdg": objective_scalars(dg_obj),
                "arr": jnp.asarray(arrivals),
                "arrs": jnp.asarray(arrivals[order]),
                "cap": jnp.asarray(cap_req),
                "wreq": jnp.asarray(weight_req),
                "shift": jnp.asarray(lat_shift),
                "seq": jnp.asarray(seq_of),
                "cls": jnp.asarray(cls_idx.astype(np.int32)),
                "clsord": jnp.asarray(cls_ord),
                "members": jnp.asarray(members),
                "wcls": jnp.asarray(w_cls),
                "child": jnp.asarray(trie.child.astype(np.int32)),
                "depth": jnp.asarray(trie.depth.astype(np.int32)),
                "eom": jnp.asarray(
                    np.asarray(td.engine_of_model).astype(np.int32)),
                "row": jnp.asarray(row),
                "tabs": jnp.asarray(tab_s),
                "tabc": jnp.asarray(tab_c),
                "tabl": jnp.asarray(tab_l),
                "conc": jnp.asarray(conc),
                "ms": jnp.asarray(ms),
                "hasm": jnp.asarray(hasm),
                "bacc": jnp.asarray(best_acc),
                "mcost": jnp.asarray(min_cost),
                "edges": jnp.asarray(sketch.edges),
            }
            if tokens:
                # added only under the token calendar so legacy configs keep
                # their exact operand pytree (and compiled-program cache keys)
                cn["tkw"] = jnp.asarray(tkw)
                cn["tkv"] = jnp.asarray(tkv)
                cn["tkf"] = jnp.asarray(tkf)
                cn["tkc"] = jnp.asarray(tkc)
                cn["tk1"] = jnp.asarray(tk1)
            if explore_model is not None:
                cn["xpm"] = jnp.asarray(explore_model)
            if fault_outages:
                # transition columns, padded with one sentinel row so the
                # traced cursor clip reads (inf, engine 0, up) past the end
                fev = faults.events(engines)
                cn["ftt"] = jnp.asarray(
                    np.array([t for t, _, _ in fev] + [np.inf]))
                cn["fte"] = jnp.asarray(
                    np.array([ei for _, ei, _ in fev] + [0], dtype=np.int32))
                cn["ftu"] = jnp.asarray(
                    np.array([up for _, _, up in fev] + [True], dtype=bool))
            if fault_failures:
                cn["fdr"] = jnp.asarray(faults.failure_draws(B, max_depth))
                cn["fbo"] = jnp.asarray(
                    np.array([faults.backoff(a)
                              for a in range(int(faults.max_retries) + 1)]))
            st = _init_state(jnp, cfg, B, arrivals[order])

        with _host_span(host_s, "enqueue", requests=B):
            signature = _call_signature(cfg, st, cn)
            epochs = 0
            arrs = arrivals[order]
            chunk = max(int(epoch), 1)
            pos = 0
            si = 0
            while True:
                pos2 = min(pos + chunk, B)
                t_arr_hi = np.inf if pos2 >= B else float(arrs[pos2 - 1])
                if si < len(swaps) and swaps[si][0] < t_arr_hi:
                    # annotation-version swap: run the current program up to
                    # the swap time (events at t <= t_swap stay under the old
                    # annotations — same rule as the host loop), then
                    # substitute the new TrieDevice operand.  t_hi and the
                    # annotation columns are traced operands, so the swap
                    # compiles ZERO new programs.
                    st = step(st, cn, float(swaps[si][0]))
                    epochs += 1
                    cn = {**cn, "td": swaps[si][1]}
                    si += 1
                    continue
                st = step(st, cn, t_arr_hi)
                epochs += 1
                pos = pos2
                if pos >= B:
                    # arrivals exhausted: one final unbounded epoch drains
                    # every remaining completion/deadline event
                    break
            _ENGINE_CALLS.setdefault(signature, step)
        stats.annotation_swaps = si
        stats.epochs = epochs
        # every readback below would wait for the device; waiting here
        # first tells the device's time from the drain's
        with _host_span(host_s, "wait", requests=B):
            jax.block_until_ready(st)
        with _host_span(host_s, "drain", requests=B) as span:
            n_done = int(st["don"])
            if n_done != B:
                raise RuntimeError(
                    f"compiled event loop stalled with work outstanding "
                    f"({n_done}/{B} requests terminal)")

            stats.events = int(st["ev"])
            stats.replans = int(st["rp"])
            stats.sweeps = int(st["swp"])
            stats.admitted = int(st["adm"])
            stats.rejected = int(st["rej"])
            stats.shed = int(st["shd"])
            stats.downgraded = int(st["dgc"])
            stats.preemptions = int(st["pre"])
            stats.resumed = int(st["res"])
            stats.explored = int(st["xpc"])
            if fault_outages:
                stats.engine_outages = int(st["foc"])
                stats.engine_recoveries = int(st["frc"])
                stats.checkpointed = int(st["fck"])
            if fault_failures:
                stats.stage_failures = int(st["fsc"])
                stats.fault_retries = int(st["frt"])
            if fault_outages or fault_failures:
                stats.failed = int(st["ffc"])
            stats.peak_occupancy = {
                e: int(v) for e, v in zip(engines, np.asarray(st["po"]))}
            sketch.merge_counts(np.asarray(st["hist"]), edges=sketch.edges)
            calendar = {}
            if tokens:
                stats.batched_s = float(st["bts"])
                stats.over_cap_s = float(st["ocs"])
                calendar = {"batched_s": stats.batched_s,
                            "over_cap_s": stats.over_cap_s}
            span.set_metadata(events=stats.events, sweeps=stats.sweeps,
                              epochs=stats.epochs, **calendar)
            if stream:
                # constant-memory path: per-request columns stay on device and
                # are never materialized as host-side python lists; the summary
                # is O(1) scalars + the fixed-size quantile histogram (carried
                # under "sketch" so shard drains merge exactly)
                summary = {
                    "n_requests": B,
                    "events": stats.events,
                    "replans": stats.replans,
                    "served": B - stats.rejected - stats.shed - stats.failed,
                    "succeeded": int(jnp.sum(st["rsc"])),
                    "rejected": stats.rejected,
                    "shed": stats.shed,
                    "failed": stats.failed,
                    "slo_violations": int(st["slo"]),
                    "latency": _wf(st["lw"]),
                    "cost": _wf(st["cw"]),
                    "latency_p50": sketch.quantile(0.5),
                    "latency_p95": sketch.quantile(0.95),
                    "latency_p99": sketch.quantile(0.99),
                    "sketch": sketch.state(),
                    "sweeps": stats.sweeps,
                    "epochs": stats.epochs,
                    **calendar,
                    # the same dict as stats.host_s: the drain's own time
                    # is added to it as this span closes
                    "host_s": host_s,
                }
                stats.preempt_count = np.zeros(0, dtype=np.int64)
                stats.outcome = []
                return summary, stats

            roc = np.asarray(st["roc"])
            rsc = np.asarray(st["rsc"])
            rct = np.asarray(st["rct"])
            ru = np.asarray(st["ru"])
            stats.done_t = np.asarray(st["rdn"]).copy()
            stats.admit_t = np.asarray(st["rad"]).copy()
            stats.preempt_count = np.asarray(st["rpc"]).astype(np.int64)
            stats.outcome = [_OUTCOMES[int(o)] for o in roc]
            results = []
            for i in range(B):
                lat = float(stats.done_t[i] - stats.arrival_t[i])
                slo = bool(np.isfinite(cap_req[i])) and lat > cap_req[i] + _SLO_TOL
                mods = trie.path(int(ru[i]))
                results.append(ExecutionResult(
                    success=bool(rsc[i]),
                    total_cost=float(rct[i]),
                    total_lat=lat,
                    models=mods,
                    n_stages=len(mods),
                    replan_overhead_s=0.0,
                    slo_violated=slo,
                    outcome=stats.outcome[i],
                ))
            return results, stats


def _wf(wt) -> dict:
    """Finalize a traced Welford triple into host floats."""
    from repro.core.streaming import welford_finalize
    return welford_finalize(tuple(float(x) for x in wt))


def _empty_summary(stats: EventStats) -> dict:
    from repro.core.streaming import welford_finalize, welford_init
    z = welford_finalize(welford_init())
    return {"n_requests": 0, "events": 0, "replans": 0, "served": 0,
            "succeeded": 0, "rejected": 0, "shed": 0, "failed": 0,
            "slo_violations": 0,
            "latency": z, "cost": z, "latency_p50": float("nan"),
            "latency_p95": float("nan"), "latency_p99": float("nan"),
            "sketch": QuantileSketch.log_spaced().state(),
            "sweeps": 0, "epochs": 0, "host_s": stats.host_s}


def _init_state(jnp, cfg: _EngineConfig, B: int, arrs_sorted: np.ndarray):
    """Device state pytree at t=0 (first event = first arrival)."""
    C, K, E = cfg.capacity, cfg.n_classes, cfg.n_engines
    P = cfg.paused_cap
    i32, i64, f64 = jnp.int32, jnp.int64, jnp.float64
    st = {
        "tn": jnp.asarray(float(arrs_sorted[0]), f64),
        "tl": jnp.asarray(0.0, f64),
        "ap": jnp.asarray(0, i64),
        "ns": jnp.asarray(0, i64),
        "wtd": jnp.asarray(False),
        "ev": jnp.asarray(0, i64), "rp": jnp.asarray(0, i64),
        "swp": jnp.asarray(0, i64),
        "adm": jnp.asarray(0, i64), "rej": jnp.asarray(0, i64),
        "shd": jnp.asarray(0, i64), "dgc": jnp.asarray(0, i64),
        "pre": jnp.asarray(0, i64), "res": jnp.asarray(0, i64),
        "don": jnp.asarray(0, i64), "slo": jnp.asarray(0, i64),
        "po": jnp.zeros(E, i64),
        "so": jnp.full(C, -1, i32),
        "su": jnp.zeros(C, i32),
        "sec": jnp.zeros(C, f64),
        "sm": jnp.full(C, -1, i32),
        "sok": jnp.zeros(C, bool),
        "sdg": jnp.zeros(C, bool),
        "sfree": jnp.ones(C, bool),
        "snd": jnp.zeros(C, bool),
        "sddl": jnp.full(C, jnp.inf, f64),
        "je": jnp.full(C, -1, i32),
        "jsq": jnp.zeros(C, i64),
        "jtc": jnp.full(C, jnp.inf, f64),
        "jwk": jnp.zeros(C, f64),
        "jrm": jnp.full(C, jnp.inf, f64),
        "jw": jnp.ones(C, f64),
        "qh": jnp.zeros(K, i32),
        "qt": jnp.zeros(K, i32),
        "roc": jnp.full(B, _OC_SERVED, i32),
        "rsc": jnp.zeros(B, bool),
        "rct": jnp.zeros(B, f64),
        "rdn": jnp.zeros(B, f64),
        "rad": jnp.zeros(B, f64),
        "ru": jnp.zeros(B, i32),
        "rpc": jnp.zeros(B, i32),
        "lw": (jnp.asarray(0.0, f64), jnp.asarray(0.0, f64),
               jnp.asarray(0.0, f64)),
        "cw": (jnp.asarray(0.0, f64), jnp.asarray(0.0, f64),
               jnp.asarray(0.0, f64)),
        "hist": jnp.zeros(cfg.n_bins, i64),
        "xpc": jnp.asarray(0, i64),
    }
    if cfg.priorities or cfg.fault_outages:
        st.update({
            "pb": jnp.full((K, P), -1, i32),
            "pn": jnp.zeros(K, i32),
            "rpu": jnp.zeros(B, i32),
            "rpm": jnp.zeros(B, i32),
            "rpok": jnp.zeros(B, bool),
            "rprm": jnp.zeros(B, f64),
            "rpec": jnp.zeros(B, f64),
            "rpdg": jnp.zeros(B, bool),
            "rpp": jnp.zeros(B, bool),
        })
    if cfg.fault_outages or cfg.fault_failures:
        st.update({
            "rfl": jnp.zeros(B, bool),
            "rpat": jnp.zeros((B, cfg.max_depth), i64),
            "ffc": jnp.asarray(0, i64),
        })
    if cfg.fault_outages:
        st.update({
            "av": jnp.ones(E, bool),
            "fi": jnp.asarray(0, i32),
            "foc": jnp.asarray(0, i64),
            "frc": jnp.asarray(0, i64),
            "fck": jnp.asarray(0, i64),
        })
    if cfg.fault_failures:
        st.update({
            "srt": jnp.full(C, jnp.inf, f64),
            "fsc": jnp.asarray(0, i64),
            "frt": jnp.asarray(0, i64),
        })
    if cfg.pol.wants_forecast:
        st["dead"] = jnp.zeros(B, bool)
    if cfg.tokens:
        # engine-seconds batching under a KV cap, and above one
        st["bts"] = jnp.asarray(0.0, f64)
        st["ocs"] = jnp.asarray(0.0, f64)
    return st


def merge_stream_summaries(a: dict, b: dict) -> dict:
    """Fold two streaming summaries (e.g. per-shard drains of a sharded
    replay) into one — the merge is EXACT: counters add, Welford moments
    combine via Chan's parallel update, and the quantile sketches (each
    summary carries its histogram under ``"sketch"``) merge bin-by-bin
    before the p50/p95/p99 fields are recomputed from the merged counts.
    Sketch merging validates the bin edges bitwise and raises
    ``ValueError`` when the two summaries were accumulated over different
    binnings (or when only one side carries a sketch) — a silent merge of
    incompatible histograms would corrupt every reported quantile.

    The tracing counters ``sweeps``, ``epochs``, the token calendar's
    ``batched_s`` and ``over_cap_s`` and the per-phase ``host_s`` seconds
    add too, where either side carries them."""
    out = dict(a)
    for key in ("n_requests", "events", "replans", "served", "succeeded",
                "rejected", "shed", "failed", "slo_violations"):
        out[key] = a[key] + b[key]
    for key in ("sweeps", "epochs", "batched_s", "over_cap_s"):
        if key in a or key in b:
            out[key] = a.get(key, 0) + b.get(key, 0)
    if "host_s" in a or "host_s" in b:
        ha, hb = a.get("host_s", {}), b.get("host_s", {})
        out["host_s"] = {k: ha.get(k, 0.0) + hb.get(k, 0.0)
                         for k in {**ha, **hb}}
    for key in ("latency", "cost"):
        wa = (a[key]["count"], a[key]["mean"], a[key]["var"] * a[key]["count"])
        wb = (b[key]["count"], b[key]["mean"], b[key]["var"] * b[key]["count"])
        c, m, m2 = welford_merge(wa, wb)
        var = m2 / c if c > 0 else 0.0
        out[key] = {"count": c, "mean": m, "var": var,
                    "std": float(np.sqrt(max(var, 0.0)))}
    has_a, has_b = "sketch" in a, "sketch" in b
    if has_a != has_b:
        raise ValueError(
            "cannot merge stream summaries: only one side carries a "
            "quantile sketch — quantiles are not mergeable from the "
            "finalized p50/p95/p99 fields alone")
    if has_a:
        sk = QuantileSketch.from_state(a["sketch"])
        sk.merge(QuantileSketch.from_state(b["sketch"]))  # validates edges
        out["sketch"] = sk.state()
        out["latency_p50"] = sk.quantile(0.5)
        out["latency_p95"] = sk.quantile(0.95)
        out["latency_p99"] = sk.quantile(0.99)
    return out
