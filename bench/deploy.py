"""The system under test, set up from a configuration file.

Builds the program's own objects (workflow template, trie, workload,
exact annotations, stage executor, and the engines' load model) from the
configuration and the seeded question tables, and wraps the served entry
``run_events(..., compiled=True, stream=True)`` as one call per segment of
a traffic mix.  This is the only module of the benchmark that imports the
program.

The load model is the processor-sharing ``FleetLoadModel`` at the
configuration's ``engine_concurrency``, or, for a configuration with a
``fleet`` section, the program's token calendar (``TokenWorkModel``), each
engine's decode curve built by the program from its published
architecture (`engine_model`).
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
    # fleet configurations: prompt tokens per stage, and the question
    # table's decode tokens (question, stage, model)
    prompt_tokens: tuple | None = None
    decode: np.ndarray | None = None

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

        kwargs = self.kwargs
        if self.decode is not None:
            prompt, decode = self.prompt_tokens, self.decode

            def stage_tokens(p, depth, model):
                return prompt[depth], float(decode[qids[p], depth, model])

            kwargs = dict(kwargs, work_model=dataclasses.replace(
                kwargs["work_model"], stage_tokens=stage_tokens))
        summary, _ = run_events(self.trie, self.ann, self.obj,
                                np.arange(len(qids)), executor,
                                arrivals=arrivals, compiled=True,
                                stream=True, epoch=epoch, **kwargs)
        return summary


# the published keys of a dense GQA/MHA block, as the program's
# `ArchConfig` names them
DENSE_KEYS = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
              "num_attention_heads": "n_heads",
              "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
              "intermediate_size": "d_ff", "vocab_size": "vocab",
              "tie_word_embeddings": "tie_embeddings"}


def from_published(name: str, published: dict, *, chip: dict,
                   replica_chips: int, kv_budget_bytes: float,
                   context_len: int, bytes_per_param: float):
    """The program's `EngineTokenModel` of an engine serving ``published``
    on a replica of ``replica_chips`` chips, through the program's own
    roofline (`EngineTokenModel.from_roofline` over its `ArchConfig`
    count, the replica's peaks summed over its chips).  It stands in for
    the program entry ``EngineTokenModel.from_published``, which the
    program does not have yet, and models a dense GQA/MHA block only."""
    from repro.models.config import ArchConfig
    from repro.serving.loadsim import EngineTokenModel

    unmodeled = sorted(set(published) - set(DENSE_KEYS))
    if unmodeled:
        raise NotImplementedError(
            f"{name}: the program models dense GQA/MHA engines only; it "
            f"does not model {', '.join(unmodeled)}")
    arch = {DENSE_KEYS[k]: v for k, v in published.items()}
    arch.setdefault("n_kv_heads", arch["n_heads"])
    return EngineTokenModel.from_roofline(
        name, ArchConfig(name=name, family="dense", **arch),
        context_len=context_len, kv_budget_bytes=kv_budget_bytes,
        bytes_per_param=bytes_per_param,
        peak_flops=replica_chips * float(chip["peak_flops"]),
        hbm_bw=replica_chips * float(chip["hbm_bw"]))


def engine_model(name: str, fleet: dict):
    """Engine ``name`` of a ``fleet`` section as the program builds it:
    through the program's ``EngineTokenModel.from_published`` where the
    program has that entry, else through `from_published` here."""
    from repro.serving.loadsim import EngineTokenModel

    e = fleet["engines"][name]
    entry = getattr(EngineTokenModel, "from_published", from_published)
    return entry(name, e["published"], chip=fleet["chip"],
                 replica_chips=int(e["replica_chips"]),
                 kv_budget_bytes=float(e["kv_budget_bytes"]),
                 context_len=int(e["context_len"]),
                 bytes_per_param=float(fleet["bytes_per_param"]))


def build(config: dict, tables) -> Deployment:
    """The program's deployment of ``config`` over ``tables`` (S, cost,
    lat, and decode tokens for a fleet configuration); the objective's
    latency cap is the configuration's quantile of the program's
    terminal-plan latencies."""
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
    from repro.serving.loadsim import (
        EngineLoadModel,
        FleetLoadModel,
        TokenWorkModel,
    )

    wf = config["workflow"]
    fleet = config.get("fleet")
    # a fleet's engines time each stage by their curves; the scalar
    # latency model is read by nothing on the served path
    no_lat = {} if fleet is None else {"base_latency": float("nan"),
                                       "per_token_latency": float("nan")}
    models = tuple(ModelSpec(**m, **no_lat) for m in wf["models"])
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
    S, cost, lat = tables[:3]
    wl = Workload(template=tpl, S=S, cost=cost, lat=lat,
                  difficulty=np.zeros(S.shape[0]))
    trie = Trie.build(tpl)
    ann = wl.exact_annotations(trie)
    engines = sorted({m.engine for m in models})
    mean_service = {
        e: float(np.mean(lat[:, :, [j for j, m in enumerate(models)
                                    if m.engine == e]]))
        for e in engines}
    if fleet is None:
        conc = int(config["engine_concurrency"])
        load = {"fleet_load": FleetLoadModel(
            engines={e: EngineLoadModel(e, concurrency=conc, jitter=0.0)
                     for e in engines},
            mean_service_s=mean_service)}
    else:
        load = {"work_model": TokenWorkModel(
            engines={e: engine_model(e, fleet) for e in engines},
            mean_service_s=mean_service)}
    obj_cfg = config["objective"]
    lat_cap = np.quantile(ann.lat[trie.terminal],
                          float(obj_cfg["lat_cap_quantile"]))
    obj = Objective(obj_cfg["kind"], lat_cap=float(lat_cap))
    devices = int(config["devices"])
    kwargs = dict(capacity=int(config["capacity"]), policy=config["policy"],
                  **load, admission=config["admission"],
                  devices=devices if devices > 1 else None)
    tokens = {} if fleet is None else {
        "prompt_tokens": tuple(float(p) for p in fleet["prompt_tokens"]),
        "decode": tables[3]}
    return Deployment(trie=trie, ann=ann, obj=obj,
                      executor=make_workload_executor(wl), kwargs=kwargs,
                      **tokens)
