"""The per-layer readers and the trace reduction, on a hand-made trace
and on a small trace recorded on one v5e chip (``data/``)."""
import os

import pytest

import run
import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DEV = "/device:TPU:0"


def _ctx(trace, events=10, calls=2, devices=(DEV,)):
    return run.ReadContext(trace=trace, devices=list(devices), events=events,
                           calls=calls)


@pytest.fixture
def made():
    return xplane.Trace(
        spans=[("bench.call", 0, 1000), ("bench.inputs", 1000, 2000),
               ("bench.call", 2000, 3000)],
        ops={DEV: [("fusion.1", 100, 300), ("fusion.2", 250, 500),
                   ("all-reduce.3", 600, 700), ("fusion.1", 2100, 2900)]},
        modules={DEV: [("jit_step(7)", 100, 900),
                       ("jit_step(7)", 2100, 2900),
                       ("jit_other(1)", 1200, 1300)]})


def test_interval_helpers():
    iv = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40)]
    assert xplane.union_ns(iv, 0, 100) == 30
    assert xplane.union_ns(iv, 8, 35) == 17
    assert xplane.idle_gaps(iv, 0, 50) == [(20, 30), (40, 50)]
    assert xplane.idle_gaps([], 0, 5) == [(0, 5)]


def test_readers_on_a_made_trace(made):
    ctx = _ctx(made)
    busy = 400 + 100 + 800
    assert run.load_reader("idle_share")(ctx) == pytest.approx(
        100 * (1 - busy / 3000))
    assert run.load_reader("step_us_per_event")(ctx) == pytest.approx(
        1600 / 1e3 / 10)
    assert run.load_reader("host_ms_per_call")(ctx) == pytest.approx(
        200 / 1e6)
    assert run.load_reader("allreduce_share")(ctx) == pytest.approx(
        100 * 100 / 3000)


def test_readers_find_nothing_without_a_device(made):
    ctx = _ctx(made, devices=())
    for name in ("idle_share", "step_us_per_event", "host_ms_per_call",
                 "allreduce_share"):
        assert run.load_reader(name)(ctx) is None
    empty = xplane.Trace(spans=[], ops={}, modules={})
    assert run.load_reader("idle_share")(_ctx(empty)) is None


def test_allreduce_share_is_silent_on_one_chip(made):
    made.ops[DEV] = [o for o in made.ops[DEV] if "all-reduce" not in o[0]]
    assert run.load_reader("allreduce_share")(_ctx(made)) is None


def test_breakdown_names_gaps_by_harness_span(made):
    b = run._breakdown(made, [DEV])
    assert b["device_ops"][0][0] == "fusion.1"
    names = dict((n, s) for n, s in b["idle_gaps"])
    assert "bench.inputs" in names


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A traced run of one 64-request call of ``mathqa4.replay``, recorded
    on one v5e chip (``data/tiny.xplane.pb.xz``)."""
    import lzma

    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with lzma.open(os.path.join(DATA, "tiny.xplane.pb.xz")) as f:
        path.write_bytes(f.read())
    return xplane.load(str(path))


def test_recorded_trace_has_what_the_readers_need(recorded):
    assert list(recorded.modules) == [DEV]
    assert any(n.startswith(xplane.STEP_MODULE)
               for n, _, _ in recorded.modules[DEV])
    assert len(recorded.ops[DEV]) > 1000
    assert len(recorded.calls()) == 1
    lo, hi = recorded.window()
    assert 0 < hi - lo < 10e9


def test_readers_on_the_recorded_trace(recorded):
    ctx = _ctx(recorded, events=100, calls=1)
    # the readings the run printed on the chip
    assert run.load_reader("idle_share")(ctx) == pytest.approx(
        50.365683412174924, rel=1e-9)
    assert run.load_reader("host_ms_per_call")(ctx) == pytest.approx(
        77.397989, rel=1e-9)
    step_ns = xplane.union_ns(
        [m for m in recorded.modules[DEV]
         if m[0].startswith(xplane.STEP_MODULE)], *recorded.window())
    assert run.load_reader("step_us_per_event")(ctx) == pytest.approx(
        step_ns / 1e3 / 100)
    assert run.load_reader("allreduce_share")(ctx) is None
    b = run._breakdown(recorded, [DEV])
    assert b["device_ops"] and all(" = " not in n for n, _ in b["device_ops"])
    assert b["idle_gaps"][0][1] > 0
