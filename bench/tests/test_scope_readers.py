"""The readers of the program's scopes and spans (`scopes.py`), on a
hand-made trace with a scope map, and on the parent's case: a program
without scopes, where every one of them finds nothing."""
import os

import numpy as np
import pytest

import run
import scopes
import xplane

DEV = "/device:TPU:0"
STEP = "jit(step)/while/body/"
SMAP = {
    "while.1": "jit(step)/while",
    "fusion.2": STEP + "vinelm/clock/add",
    "while.3": STEP + "vinelm/admit/while",
    "fusion.4": STEP + "vinelm/admit/while/body/select_n",
    "conditional.5": STEP + "vinelm/admit/while/body/vinelm/dispatch/cond",
    "fusion.6": STEP + "vinelm/admit/while/body/vinelm/dispatch/cond/mul",
    "while.7": STEP + "vinelm/admit/while/body/vinelm/dispatch/cond/while"
               "/body/vinelm/plan/while",
    "fusion.8": STEP + "vinelm/admit/while/body/vinelm/dispatch/cond/while"
                "/body/vinelm/plan/while/body/min",
    "fusion.9": STEP + "vinelm/clock/lt",
}
READERS = ("clock_us_per_event", "admit_us_per_event",
           "dispatch_us_per_event", "plan_us_per_event", "plan_roofline")
# what the run that recorded ``data/scoped.xplane.pb.xz`` printed on the chip
RECORDED = {
    "clock_us_per_event": 198.73309278350516,
    "admit_us_per_event": 45.31749484536082,
    "dispatch_us_per_event": 97.23617525773196,
    "plan_us_per_event": 88.54738144329896,
    "plan_roofline": 0.3478511004797823,
}


def _op(name, s, e):
    return (f"%{name} = f32[4]{{0}} op(f32[4]{{0}} %p)", s, e)


@pytest.fixture
def made(monkeypatch):
    trace = xplane.Trace(
        spans=[("bench.call", 0, 1000)],
        ops={DEV: [_op("while.1", 100, 900), _op("fusion.2", 110, 200),
                   _op("while.3", 200, 700), _op("fusion.4", 210, 260),
                   _op("conditional.5", 260, 650), _op("fusion.6", 270, 300),
                   _op("while.7", 300, 600), _op("fusion.8", 310, 590),
                   _op("fusion.9", 700, 800), _op("copy.10", 800, 850),
                   # another program's operation, outside the step
                   _op("fusion.2", 950, 990)]},
        modules={DEV: [("jit_step(3)", 100, 900),
                       ("jit_convert(4)", 940, 995)]})
    spans = [("vinelm.build", 5, 50, {"requests": 8, "nodes": 5461,
                                       "dmax": 6, "models": 4,
                                       "engines": 3}),
             ("vinelm.drain", 920, 930, {"requests": 8, "events": 10,
                                          "sweeps": 7, "epochs": 2})]
    monkeypatch.setattr(scopes, "scope_map", lambda: SMAP)
    monkeypatch.setattr(scopes, "program_spans", lambda: spans)
    monkeypatch.setattr(scopes, "device_kind", lambda: "TPU v5 lite")
    return trace


def _ctx(trace, events=10, devices=(DEV,)):
    return run.ReadContext(trace=trace, devices=list(devices), events=events,
                           calls=1)


def test_scope_self_times_on_a_made_trace(made):
    t = scopes.times(_ctx(made))
    assert t["step"] == 800
    assert t["clock"] == 90 + 100
    # the admit loop [200, 700] less the dispatch round nested in it
    assert t["admit"] == 500 - 390
    assert t["dispatch"] == 390 - 300
    assert t["plan"] == 300
    assert t["scoped"] == 690
    assert sum(t[s] for s in scopes.SCOPES) == t["scoped"]


def test_readers_on_a_made_trace(made):
    ctx = _ctx(made)
    read = {n: run.load_reader(n)(ctx) for n in READERS}
    assert read["clock_us_per_event"] == pytest.approx(190 / 1e3 / 10)
    assert read["admit_us_per_event"] == pytest.approx(110 / 1e3 / 10)
    assert read["dispatch_us_per_event"] == pytest.approx(90 / 1e3 / 10)
    assert read["plan_us_per_event"] == pytest.approx(300 / 1e3 / 10)
    pw = run.load_reader("plan_roofline").__globals__["plan_work"]
    ops, nbytes = pw.sweep_work(5461, 6, 4, 3)
    assert read["plan_roofline"] == pytest.approx(
        pw.roofline_share(7 * ops, 7 * nbytes, 300e-9, "TPU v5 lite")[0])


def test_the_scope_times_average_over_chips(made):
    two = "/device:TPU:1"
    made.ops[two] = [(n, s + 5, e + 5) for n, s, e in made.ops[DEV]]
    made.modules[two] = [(n, s + 5, e + 5) for n, s, e in made.modules[DEV]]
    t = scopes.times(_ctx(made, devices=(DEV, two)))
    # the second chip's operations, 5 ns later, take as long
    assert (t["plan"], t["clock"]) == (300, 190)
    share = run.load_reader("plan_roofline")(_ctx(made, devices=(DEV, two)))
    one = run.load_reader("plan_roofline")(_ctx(made))
    assert share == pytest.approx(one / 2)


def test_without_the_programs_scopes_the_readers_find_nothing(
        made, monkeypatch):
    monkeypatch.setattr(scopes, "scope_map", lambda: None)
    for name in READERS:
        assert run.load_reader(name)(_ctx(made)) is None


def test_without_the_programs_spans_plan_roofline_finds_nothing(
        made, monkeypatch):
    monkeypatch.setattr(scopes, "program_spans", lambda: [])
    assert run.load_reader("plan_roofline")(_ctx(made)) is None
    assert run.load_reader("plan_us_per_event")(_ctx(made)) is not None


def test_without_a_device_the_readers_find_nothing(made, monkeypatch):
    def no_map():
        raise AssertionError("the map is built only for a device trace")

    monkeypatch.setattr(scopes, "scope_map", no_map)
    for name in READERS:
        assert run.load_reader(name)(_ctx(made, devices=())) is None
    empty = xplane.Trace(spans=[("bench.call", 0, 10)], ops={}, modules={})
    for name in READERS:
        assert run.load_reader(name)(_ctx(empty)) is None


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_union_matches_the_harness_helper(seed):
    rng = np.random.default_rng(seed)
    s = rng.integers(0, 1000, 200)
    e = s + rng.integers(1, 60, 200)
    iv = [(None, int(a), int(b)) for a, b in zip(s, e)]
    assert scopes._union(s, e) == xplane.union_ns(iv, 0, 2000)


def test_chain_names_the_scopes_outermost_first():
    assert scopes.chain(SMAP["fusion.8"]) == ("admit", "dispatch", "plan")
    assert scopes.chain(SMAP["while.1"]) == ()
    assert scopes.chain("jit(f)/vinelm/planner/x") == ()


def test_the_trace_directory_is_the_harness_one():
    assert scopes.TRACE_DIR == os.path.join(run.ROOT, run.TRACE_DIR)


def test_program_spans_of_a_traced_call_on_the_cpu(tmp_path, monkeypatch):
    """The spans the program writes, read back from the trace file."""
    import json

    import jax

    import deploy
    import gen

    with open(os.path.join(run.ROOT, "bench", "configs",
                           "nl2sql_2.c64.json")) as f:
        config = json.load(f)
    with open(os.path.join(run.ROOT, "bench", "traffic", "burst6.json")) as f:
        mix = {**json.load(f), "requests_per_call": 40}
    wf = config["workflow"]
    nq = int(config["questions"])
    dep = deploy.build(config, gen.question_tables(
        wf["models"], len(wf["stages"]), nq, int(config["questions_seed"])))
    reqs, arr = gen.call_inputs(mix, nq, 3, 0)
    dep.call(reqs, arr, epoch=16)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.call"):
            summary = dep.call(reqs, arr, epoch=16)
    spans = scopes.program_spans(str(tmp_path))
    assert [n for n, *_ in spans] == [
        "vinelm." + p for p in ("build", "tabulate", "upload", "enqueue",
                                "wait", "drain")]
    drain = spans[-1][3]
    assert (drain["events"], drain["sweeps"], drain["epochs"]) == (
        summary["events"], summary["sweeps"], summary["epochs"])
    trace = xplane.load(xplane.find(str(tmp_path)))
    assert len(trace.calls()) == 1
    monkeypatch.setattr(scopes, "program_spans", lambda: spans)
    calls = scopes.traced_calls(_ctx(trace))
    assert calls["sweeps"] == summary["sweeps"] > 0
    assert calls["nodes"] == dep.trie.n_nodes
    assert calls["models"] == len(wf["models"])


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    """A traced run of one 64-request call of ``mathqa4.replay`` with the
    program's scopes and spans, recorded on one v5e chip, and the scope
    map the program built for it (``data/scoped.*``)."""
    import json
    import lzma

    d = tmp_path_factory.mktemp("scoped")
    data = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
    with lzma.open(os.path.join(data, "scoped.xplane.pb.xz")) as f:
        (d / "scoped.xplane.pb").write_bytes(f.read())
    with lzma.open(os.path.join(data, "scoped.scope_map.json.xz")) as f:
        smap = json.load(f)
    return xplane.load(str(d / "scoped.xplane.pb")), smap, str(d)


def test_readers_on_the_recorded_scoped_trace(scoped, monkeypatch):
    trace, smap, d = scoped
    spans = scopes.program_spans(d)
    assert [n for n, *_ in spans] == [
        "vinelm." + p for p in ("build", "tabulate", "upload", "enqueue",
                                "wait", "drain")]
    events = spans[-1][3]["events"]
    monkeypatch.setattr(scopes, "scope_map", lambda: smap)
    monkeypatch.setattr(scopes, "program_spans", lambda: spans)
    monkeypatch.setattr(scopes, "device_kind", lambda: "TPU v5 lite")
    ctx = _ctx(trace, events=events)
    for name, value in RECORDED.items():
        assert run.load_reader(name)(ctx) == pytest.approx(value, rel=1e-9)
    t = scopes.times(ctx)
    assert t["scoped"] >= 0.99 * t["step"]
    assert sum(t[s] for s in scopes.SCOPES) == pytest.approx(t["scoped"])
    # the map names the step's operations; those it leaves out (the
    # entry's operand copies, which carry no metadata) are a handful
    (a, b), = [(s, e) for n, s, e in trace.modules[DEV]
               if n.startswith(xplane.STEP_MODULE)]
    names = [n.split(" = ")[0].lstrip("%") for n, s, _ in trace.ops[DEV]
             if a <= s < b]
    unnamed = [n for n in names if n not in smap]
    assert len(names) > 10000 and len(unnamed) < 1e-3 * len(names)
