"""The system under test, set up from a configuration file.

Builds the program's own objects (workflow template, trie, workload,
exact annotations, stage executor, fleet load model) from the
configuration and the seeded question tables, and wraps the served entry
``run_events(..., compiled=True, stream=True)`` as one call per segment of
a traffic mix.  This is the only module of the benchmark that imports the
program.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Deployment:
    trie: object
    ann: object
    obj: object
    executor: object
    kwargs: dict          # run_events keywords of the configuration

    def call(self, qids, arrivals, *, epoch: int, run_events=None):
        """One call of the served path: request ``p`` asks question
        ``qids[p]`` and arrives at ``arrivals[p]``; returns the streamed
        summary dict.

        The program is handed the requests as the distinct keys ``0..B-1``
        and an executor that looks each one's question up: every request
        of a deployment is distinct, and the engine's tables, whose shape
        follows the count of distinct keys, keep one shape per cell."""
        if run_events is None:
            from repro.core.events import run_events
        stage = self.executor
        qids = [int(q) for q in qids]

        def executor(p, depth, model, t_now):
            return stage(qids[p], depth, model, t_now)

        summary, _ = run_events(self.trie, self.ann, self.obj,
                                np.arange(len(qids)), executor,
                                arrivals=arrivals, compiled=True,
                                stream=True, epoch=epoch, **self.kwargs)
        return summary


def build(config: dict, tables) -> Deployment:
    """The program's deployment of ``config`` over ``tables`` (S, cost,
    lat); the objective's latency cap is the configuration's quantile of
    the program's terminal-plan latencies."""
    from repro.core.controller import Objective
    from repro.core.runtime import make_workload_executor
    from repro.core.trie import Trie
    from repro.core.workflow import (
        DecisionPoint,
        ModelSpec,
        ToolStage,
        WorkflowTemplate,
    )
    from repro.core.workload import Workload
    from repro.serving.loadsim import EngineLoadModel, FleetLoadModel

    wf = config["workflow"]
    models = tuple(ModelSpec(**m) for m in wf["models"])
    decisions = []
    for d, st in enumerate(wf["stages"]):
        tools = ()
        if st["tool_cost"] or st["tool_latency"]:
            tools = (ToolStage("tool", cost=float(st["tool_cost"]),
                               latency=float(st["tool_latency"])),)
        decisions.append(DecisionPoint(stage=f"stage{d}", iteration=d,
                                       models=tuple(st["models"]),
                                       tools_after=tools))
    tpl = WorkflowTemplate(name=wf["name"], models=models,
                           decisions=tuple(decisions),
                           min_depth=int(wf["min_depth"]))
    S, cost, lat = tables
    wl = Workload(template=tpl, S=S, cost=cost, lat=lat,
                  difficulty=np.zeros(S.shape[0]))
    trie = Trie.build(tpl)
    ann = wl.exact_annotations(trie)
    engines = sorted({m.engine for m in models})
    mean_service = {
        e: float(np.mean(lat[:, :, [j for j, m in enumerate(models)
                                    if m.engine == e]]))
        for e in engines}
    conc = int(config["engine_concurrency"])
    load = FleetLoadModel(
        engines={e: EngineLoadModel(e, concurrency=conc, jitter=0.0)
                 for e in engines},
        mean_service_s=mean_service)
    obj_cfg = config["objective"]
    lat_cap = np.quantile(ann.lat[trie.terminal],
                          float(obj_cfg["lat_cap_quantile"]))
    obj = Objective(obj_cfg["kind"], lat_cap=float(lat_cap))
    devices = int(config["devices"])
    kwargs = dict(capacity=int(config["capacity"]), policy=config["policy"],
                  fleet_load=load, admission=config["admission"],
                  devices=devices if devices > 1 else None)
    return Deployment(trie=trie, ann=ann, obj=obj,
                      executor=make_workload_executor(wl), kwargs=kwargs)
