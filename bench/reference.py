"""Plain reference of the served control plane, for the `correct` check.

A straightforward, per-event Python simulation of the serving contract
that the program's event engines implement, written without any of the
program's code:

- the execution trie of the configuration's workflow, numbered in DFS
  preorder with children in model order, so that a subtree is the index
  interval ``[u, u + size[u])``;
- exact annotations (plan accuracy, expected cost, conditional latency)
  over the question tables;
- the planner: after each arrival or stage completion, the best
  terminating plan below the request's realized prefix, by highest
  accuracy, then lowest remaining cost, then lowest remaining latency,
  then lowest node index, among the plans whose remaining latency plus
  the live engine delays fits the request's remaining budget.  It runs in
  the configuration's ``precision.planner`` (float32), with the engine
  delays of a path accumulated model by model;
- the engine calendar: processor sharing at ``max(1, occupancy /
  concurrency)`` slowdown, drained between events on a clock of the
  configuration's ``precision.clock`` (float64);
- for a configuration with a ``fleet`` section, the token calendar
  instead: each engine's decode step over a batch of ``b`` sequences
  takes ``step(b) = max(t_w + t_kv * b, t_f * b)`` (`fleet.py`'s own
  count of the published architecture), at most ``kv_cap`` sequences
  share a step, so each of ``occ`` sequences on the engine drains its
  batch-1 seconds of work at ``(b / occ) * (step(1) / step(b))`` with
  ``b = min(max(occ, 1), kv_cap)``; the planner's delay for an engine
  with ``n`` sequences is ``(slowdown(n) - 1) x mean service``, where
  ``slowdown(n) = ((n + 1) / b) * (step(b) / step(1))`` and ``b = min(n
  + 1, kv_cap)``;
- feasibility admission: a FIFO queue over a fixed number of slots,
  rejection of queued requests whose burned budget rules out the fastest
  unloaded plan, rejection or shed when the planner finds no feasible
  plan, and sheds of in-service requests that can no longer meet their
  deadline.

`Reference.simulate` replays one call's requests and returns the same
summary that the streamed program returns: event, replan and disposition
counts, and the summed latency and cost of the served requests.  Its
controls compute in the precision below the stated one: the planner in
bfloat16, or the clock in float32.
"""
from __future__ import annotations

import collections
import math

import numpy as np

import fleet as fleet_count

SERVED, REJECTED, SHED = 0, 1, 2

PLAN_SLACK = 1e-6    # absolute float32 slack of the latency test
CERT_SLACK = 1e-9    # deadline certainty slack, seconds
DONE_TOL = 1e-9      # remaining work below which a stage has completed
SLO_TOL = 1e-9       # latency above the cap by more than this misses it
GATE_MARGIN = 1e-4   # feasibility gate's queue-reject margin, seconds
BIG = 1e30           # "no cap" sentinel of the planner
# the precisions a configuration may state, and the control of each: the
# nearest precision below it
PLANNER_CONTROL = {"float32": "bfloat16"}
CLOCK_CONTROL = {"float64": "float32"}


def round_bf16(x: np.ndarray) -> np.ndarray:
    """Float32 values rounded to bfloat16 (nearest, ties to even) and
    widened back: the operand rounding of a default-precision matmul."""
    b = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32)


class Reference:
    """One deployment: the configuration, its question tables, and all
    that follows from them."""

    def __init__(self, config: dict, tables):
        wf = config["workflow"]
        self.models = wf["models"]
        self.stages = wf["stages"]
        self.D = len(self.stages)
        self.M = len(self.models)
        S, cost, lat = tables[:3]
        self.nq = S.shape[0]
        self.capacity = int(config["capacity"])
        if config["policy"] not in ("dynamic", "dynamic_load_aware"):
            raise ValueError(f"policy {config['policy']!r}")
        self.load_aware = config["policy"] == "dynamic_load_aware"
        if config["admission"] not in ("always", "feasibility"):
            raise ValueError(f"admission {config['admission']!r}")
        self.gates = config["admission"] == "feasibility"
        fleet = config.get("fleet")
        if fleet is None:
            self.conc = float(config["engine_concurrency"])
        elif any(float(s["tool_latency"]) for s in wf["stages"]):
            raise ValueError("a fleet's token calendar times a stage by its "
                             "tokens alone: tool latency is not served")
        obj = config["objective"]
        if obj["kind"] != "max_acc":
            raise ValueError(f"objective {obj['kind']!r}")
        prec = config["precision"]
        if (prec["planner"] not in PLANNER_CONTROL
                or prec["clock"] not in CLOCK_CONTROL):
            raise ValueError(f"precision {prec!r}")
        self.precision = dict(prec)

        self._build_trie(int(wf["min_depth"]))
        tool_c = np.array([float(s["tool_cost"]) for s in self.stages])
        tool_l = np.array([float(s["tool_latency"]) for s in self.stages])
        self.acc, self.cost, self.lat = self._annotate(S, cost, lat, tool_c,
                                                       tool_l)
        term_lat = self.lat[self.terminal]
        self.lat_cap = float(np.quantile(term_lat, float(
            obj["lat_cap_quantile"])))
        self.min_path_lat = float(np.min(term_lat) - self.lat[0])

        # stage outcome tables, tool stages folded in
        self.tab_s = S.astype(bool)
        self.tab_c = cost + tool_c[None, :, None]
        self.tab_l = lat + tool_l[None, :, None]

        self.engines = sorted({m["engine"] for m in self.models})
        self.E = len(self.engines)
        self.eom = [self.engines.index(m["engine"]) for m in self.models]
        self.mean_service = [
            float(np.mean(lat[:, :, [j for j, m in enumerate(self.models)
                                     if m["engine"] == e]]))
            for e in self.engines]
        # per engine (t_w, t_kv, t_f, kv_cap, step(1)) of the token calendar
        self.tok = None
        if fleet is not None:
            curves = fleet_count.engines(fleet)
            self.tok = [(k.t_w, k.t_kv, k.t_f, k.kv_cap, k.step(1.0))
                        for k in (curves[e] for e in self.engines)]

        f32 = np.float32
        self.acc32 = self.acc.astype(f32)
        self.cost32 = self.cost.astype(f32)
        self.lat32 = self.lat.astype(f32)
        self.cap32 = f32(self.lat_cap)
        self.cost_cap_eff = f32(BIG) + f32(1e-6) * abs(f32(BIG))
        self.acc_bf = round_bf16(self.acc32)
        self.cost_bf = round_bf16(self.cost32)
        self.lat_bf = round_bf16(self.lat32)

    # ------------------------------------------------------------------
    # trie and annotations
    # ------------------------------------------------------------------
    def _build_trie(self, min_depth: int) -> None:
        parent, depth, model = [-1], [0], [-1]

        def visit(node: int, d: int) -> None:
            if d >= self.D:
                return
            for m in self.stages[d]["models"]:
                parent.append(node)
                depth.append(d + 1)
                model.append(int(m))
                visit(len(parent) - 1, d + 1)

        visit(0, 0)
        n = len(parent)
        self.n_nodes = n
        self.parent = np.array(parent)
        self.depth = np.array(depth)
        self.model = np.array(model)
        self.size = np.ones(n, dtype=np.int64)
        for v in range(n - 1, 0, -1):
            self.size[parent[v]] += self.size[v]
        self.terminal = self.depth >= min_depth
        self.child = np.full((n, self.M), -1, dtype=np.int64)
        self.path_models = np.full((n, self.D), -1, dtype=np.int64)
        self.counts = np.zeros((n, self.M), dtype=np.float32)
        for v in range(1, n):
            p = parent[v]
            self.child[p, model[v]] = v
            self.path_models[v] = self.path_models[p]
            self.path_models[v, depth[v] - 1] = model[v]
            self.counts[v] = self.counts[p]
            self.counts[v, model[v]] += 1.0

    def _annotate(self, S, cost, lat, tool_c, tool_l):
        """Exact per-plan accuracy, expected cost (a stage is paid only if
        every earlier stage failed) and latency (each stage's mean latency
        over the questions that reach it)."""
        nq, n = self.nq, self.n_nodes
        A = np.zeros((nq, n), dtype=np.uint8)
        C = np.zeros((nq, n), dtype=np.float64)
        reached = np.zeros((nq, n), dtype=np.uint8)
        failall = np.ones((nq, n), dtype=np.float64)
        for u in range(1, n):
            p, d, m = self.parent[u], self.depth[u] - 1, self.model[u]
            s = S[:, d, m].astype(np.float64)
            reached[:, u] = failall[:, p] > 0.5
            failall[:, u] = failall[:, p] * (1.0 - s)
            C[:, u] = C[:, p] + failall[:, p] * (cost[:, d, m] + tool_c[d])
            A[:, u] = (1.0 - failall[:, u]) > 0.5
        acc = A.mean(axis=0)
        exp_cost = C.mean(axis=0)
        plan_lat = np.zeros(n, dtype=np.float64)
        for u in range(1, n):
            p, d, m = self.parent[u], self.depth[u] - 1, self.model[u]
            r = reached[:, u].astype(bool)
            stage = lat[r, d, m].mean() if r.any() else lat[:, d, m].mean()
            plan_lat[u] = plan_lat[p] + stage + tool_l[d]
        return acc, exp_cost, plan_lat

    # ------------------------------------------------------------------
    # planner
    # ------------------------------------------------------------------
    def plan(self, u: int, el32: np.float32, pmd: np.ndarray,
             bf16: bool = False):
        """(target node, next model) for a request at prefix ``u`` with
        ``el32`` seconds of its budget burned, under per-model delays
        ``pmd`` (float32); (-1, -1) when no plan fits, next model -1 when
        the prefix itself is the best plan.  ``bf16`` rounds every operand
        and every intermediate to bfloat16 (the control)."""
        r = round_bf16 if bf16 else (lambda x: x)
        acc, cost, lat = ((self.acc_bf, self.cost_bf, self.lat_bf) if bf16
                          else (self.acc32, self.cost32, self.lat32))
        pmd = r(pmd)
        lo, hi = u, u + int(self.size[u])
        cnt = self.counts[lo:hi]
        delay = r(cnt[:, 0] * pmd[0])
        for m in range(1, self.M):
            delay = r(delay + r(cnt[:, m] * pmd[m]))
        d_lat = r(r(lat[lo:hi] - lat[u]) + r(delay - delay[0]))
        thr = r(r(r(self.cap32) - r(el32)) + np.float32(PLAN_SLACK))
        feas = (self.terminal[lo:hi] & (d_lat <= thr)
                & (cost[lo:hi] <= self.cost_cap_eff))
        if not feas.any():
            return -1, -1
        keys = (-acc[lo:hi], r(cost[lo:hi] - cost[u]), d_lat)
        cand = feas
        for k in keys:
            cand = cand & (k <= k[cand].min())
        tgt = lo + int(np.argmax(cand))
        if tgt == u:
            return tgt, -1
        return tgt, int(self.path_models[tgt, self.depth[u]])

    # ------------------------------------------------------------------
    # one call
    # ------------------------------------------------------------------
    def simulate(self, reqs, arrivals, *, planner: str | None = None,
                 clock: str | None = None):
        """Serve one call's requests; returns the summary dict (for a fleet
        configuration with the engine-seconds of virtual time that the
        token calendar spent batching, ``batched_s``, and above a KV cap,
        ``over_cap_s``).

        ``planner`` and ``clock`` default to the configuration's stated
        precisions.  ``planner="bfloat16"`` plans, and ``clock="float32"``
        keeps every time and remaining work, in the precision below the
        stated one: the controls that the comparison has to catch."""
        planner = planner or self.precision["planner"]
        clock = clock or self.precision["clock"]
        if planner not in ("float32", "bfloat16"):
            raise ValueError(f"planner {planner!r}")
        if clock not in ("float64", "float32"):
            raise ValueError(f"clock {clock!r}")
        bf16 = planner == "bfloat16"
        if clock == "float32":
            def c(x):
                return float(np.float32(x))
        else:
            def c(x):
                return x
        reqs = np.asarray(reqs, dtype=np.int64)
        arr = np.asarray(arrivals, dtype=np.float64)
        B, C, E = reqs.size, self.capacity, self.E
        arr_l = [c(a) for a in arr.tolist()]
        order = np.argsort(arr_l, kind="stable").tolist()
        cap = c(self.lat_cap)
        gate = c(cap - self.min_path_lat + GATE_MARGIN)
        deadline_sheds = self.gates and math.isfinite(cap)
        inf = math.inf

        owner = [-1] * C
        pre = [0] * C
        spent = [0.0] * C
        smodel = [-1] * C
        sok = [False] * C
        sddl = [inf] * C
        free = [True] * C
        # engine calendar
        je = [-1] * C
        rem = [inf] * C
        t_last = 0.0
        # per-request outputs
        outcome = [SERVED] * B
        done_t = [0.0] * B
        success = [False] * B
        total_cost = [0.0] * B
        n_stages = [0] * B
        # engine-seconds with 1 < occupancy <= KV cap, and above the cap
        n = {"events": 0, "replans": 0, "rejected": 0, "shed": 0,
             "batched_s": 0.0, "over_cap_s": 0.0}
        pending: collections.deque = collections.deque()
        ap = 0

        def rate(o, e):
            # the drain rate of each of o > 0 sequences on engine e
            if self.tok is None:
                return c(1.0 / max(1.0, (float(o - 1) + 1.0) / self.conc))
            tw, tkv, tf, cap_e, s1 = self.tok[e]
            b = min(float(o), cap_e)
            sb = c(max(c(tw + c(tkv * b)), c(tf * b)))
            return c(c(b / o) * c(s1 / sb))

        def rates():
            occ = [0] * E
            for e in je:
                if e >= 0:
                    occ[e] += 1
            return occ, [rate(o, e) if o > 0 else 1.0
                         for e, o in enumerate(occ)]

        def delay(o, e):
            # the planner's extra seconds on engine e with o sequences
            if self.tok is None:
                sd = max(1.0, (float(o) + 1.0) / self.conc)
            else:
                tw, tkv, tf, cap_e, s1 = self.tok[e]
                k = float(o) + 1.0
                b = min(k, cap_e)
                sd = (k / b) * (max(tw + tkv * b, tf * b) / s1)
            return (sd - 1.0) * self.mean_service[e]

        def advance(t):
            nonlocal t_last
            dt = c(t - t_last)
            if dt > 0.0:
                occ, r = rates()
                for s in range(C):
                    if je[s] >= 0:
                        rem[s] = c(rem[s] - c(dt * r[je[s]]))
                if self.tok is not None:
                    for e, o in enumerate(occ):
                        if o > self.tok[e][3]:
                            n["over_cap_s"] += dt
                        elif o > 1:
                            n["batched_s"] += dt
            t_last = max(t_last, t)

        def next_completion():
            _, r = rates()
            out = inf
            for s in range(C):
                if je[s] >= 0:
                    out = min(out, c(t_last + c(max(rem[s], 0.0) / r[je[s]])))
            return out

        def finish(s, t):
            i = owner[s]
            done_t[i] = t
            total_cost[i] = spent[s]
            owner[s], pre[s], spent[s], smodel[s] = -1, 0, 0.0, -1
            sddl[s] = inf
            free[s] = True

        def shed(s, t):
            if smodel[s] >= 0:
                je[s], rem[s] = -1, inf
            outcome[owner[s]] = SHED
            n["shed"] += 1
            finish(s, t)

        while True:
            t = min(arr_l[order[ap]] if ap < B else inf, next_completion(),
                    min(sddl))
            if t == inf:
                break
            n["events"] += 1
            need = [False] * C
            advance(t)
            # 1. stage completions: the work is done, or what is left of
            # it ends within the clock's resolution of t
            _, r = rates()
            for s in range(C):
                if je[s] >= 0 and (rem[s] <= DONE_TOL or c(
                        t + max(rem[s], 0.0) / r[je[s]]) <= t):
                    je[s], rem[s] = -1, inf
                    i, m = owner[s], smodel[s]
                    smodel[s] = -1
                    n_stages[i] += 1
                    pre[s] = int(self.child[pre[s], m])
                    if sok[s]:
                        success[i] = True
                        finish(s, t)
                    elif self.depth[pre[s]] >= self.D:
                        finish(s, t)
                    else:
                        need[s] = True
            # 2. deadline sheds: certainty bound, then the deadline itself
            if deadline_sheds:
                for s in range(C):
                    if owner[s] >= 0 and smodel[s] >= 0:
                        ddl = c(arr_l[owner[s]] + cap)
                        if (t >= ddl or c(t + max(rem[s], 0.0))
                                > ddl + CERT_SLACK):
                            shed(s, t)
                for s in range(C):
                    if sddl[s] <= t:
                        need[s] = False
                        shed(s, t)
            # 3. arrivals join the queue; 4. queue rejections
            while ap < B and arr_l[order[ap]] <= t:
                pending.append(order[ap])
                ap += 1
            if self.gates and math.isfinite(cap):
                kept = collections.deque()
                for i in pending:
                    if c(t - arr_l[i]) > gate:
                        outcome[i] = REJECTED
                        n["rejected"] += 1
                        done_t[i] = t
                    else:
                        kept.append(i)
                pending = kept
            # 5. admit, replan, dispatch, while freed slots can take more
            while True:
                while pending and any(free):
                    s = free.index(True)
                    free[s] = False
                    i = pending.popleft()
                    owner[s], pre[s], spent[s] = i, 0, 0.0
                    t_d = c(arr_l[i] + cap)
                    if deadline_sheds and t_d > t:
                        sddl[s] = t_d
                    need[s] = True
                lanes = [s for s in range(C) if need[s]]
                if not lanes:
                    break
                n["replans"] += 1
                pmd = np.zeros(self.M, dtype=np.float32)
                if self.load_aware:
                    occ, _ = rates()
                    row = np.array([delay(occ[e], e) for e in range(E)],
                                   dtype=np.float64).astype(np.float32)
                    pmd = row[self.eom]
                plans = [self.plan(pre[s], np.float32(t - arr_l[owner[s]]),
                                   pmd, bf16) for s in lanes]
                for s, (tgt, m) in zip(lanes, plans):
                    i = owner[s]
                    if m < 0:
                        if tgt < 0 and self.gates:
                            if n_stages[i] > 0:
                                outcome[i] = SHED
                                n["shed"] += 1
                            else:
                                outcome[i] = REJECTED
                                n["rejected"] += 1
                        finish(s, t)
                        continue
                    d, q = int(self.depth[pre[s]]), int(reqs[i])
                    spent[s] += float(self.tab_c[q, d, m])
                    smodel[s] = m
                    sok[s] = bool(self.tab_s[q, d, m])
                    je[s] = self.eom[m]
                    rem[s] = c(float(self.tab_l[q, d, m]))
                need = [False] * C
                if pending and any(free):
                    continue
                break

        served = [i for i in range(B) if outcome[i] == SERVED]
        lat = [c(done_t[i] - arr_l[i]) for i in range(B)]
        fleet = {} if self.tok is None else {
            "batched_s": n["batched_s"], "over_cap_s": n["over_cap_s"]}
        return {
            "n_requests": B,
            "events": n["events"],
            "replans": n["replans"],
            "served": len(served),
            "succeeded": sum(success),
            "rejected": n["rejected"],
            "shed": n["shed"],
            "slo_violations": sum(1 for x in lat if x > cap + SLO_TOL),
            "latency_sum": math.fsum(lat[i] for i in served),
            "cost_sum": math.fsum(total_cost[i] for i in served),
            **fleet,
        }
