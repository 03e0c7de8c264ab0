import importlib.util
import os

import pytest

PATH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "metrics",
                    "plan_work.py")
spec = importlib.util.spec_from_file_location("plan_work", PATH)
plan_work = importlib.util.module_from_spec(spec)
spec.loader.exec_module(plan_work)


def test_sweep_work_scales_with_lanes_and_nodes():
    ops1, b1 = plan_work.sweep_work(5461, 6, 4, 3, lanes=1)
    ops2, b2 = plan_work.sweep_work(5461, 6, 4, 3, lanes=2)
    assert ops2 == 2 * ops1
    assert ops1 == 5461 * (2 * 4 + plan_work.OPS_PER_NODE)
    # the node columns are read once whatever the lane count
    assert b1 == 4 * 5461 * (6 + 4 + 6) + 4 * (8 + 3 + 4)
    assert b2 - b1 == 4 * (8 + 3 + 4)
    assert plan_work.sweep_work(31, 4, 2, 2)[0] < ops1


def test_unknown_device_kind_is_an_error():
    assert plan_work.peak("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        plan_work.peak("cpu")


def test_roofline_share_names_its_bound():
    ops, nbytes = plan_work.sweep_work(5461, 6, 4, 3)
    share, bound = plan_work.roofline_share(ops, nbytes, 1e-5, "TPU v5 lite")
    assert bound == "memory"
    assert share == pytest.approx(100 * nbytes / 819e9 / 1e-5)
    with pytest.raises(ValueError):
        plan_work.roofline_share(ops, nbytes, 0.0, "TPU v5 lite")
