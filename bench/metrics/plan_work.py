"""Work of one replan sweep, and the chip's peaks: the roofline's yardstick.

`sweep_work` counts the operations and bytes that one sweep of the
planner over a trie needs, from the trie's shapes alone, whatever variant
(dense, fused, Pallas) implements it.  Per lane and node: the path's
engine delay (a multiply and an add per model), the remaining latency
and cost (three adds and a subtract), six feasibility comparisons and
the three-key lexicographic narrowing (a compare and a select per key).
Per sweep, the node columns are read once (terminal, depth, accuracy,
cost, latency, blocked depth, the per-model path counts and the path's
models, 4 bytes each) and per lane a few scalars move.

`PEAKS` holds the published peaks of each chip, keyed by the
``device_kind`` that JAX reports; a kind that is not in the table is an
error.  `roofline_share` is the least time the chip could take, the
larger of operations over peak operations and bytes over peak bandwidth,
over the measured time.  This module reports no metric of its own: a
``plan_roofline`` reader divides by it once the planner's operations can
be told apart in a device trace.
"""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9,
                    "source": "Google Cloud, TPU v5e: 197 TFLOP/s bf16, "
                              "819 GB/s HBM"},
}

OPS_PER_NODE_MODEL = 2      # delay contraction: multiply and add
OPS_PER_NODE = 4 + 6 + 6    # d_lat, d_cost; feasibility; 3-key narrowing
NODE_COLUMNS = 6            # terminal, depth, acc, cost, lat, blocked
LANE_SCALARS = 8            # prefix, budgets, delays row, target, next


def sweep_work(n_nodes: int, dmax: int, n_models: int, n_engines: int,
               lanes: int = 1) -> tuple[float, float]:
    """(operations, bytes) of one sweep of ``lanes`` requests over a trie
    of ``n_nodes`` nodes, paths of up to ``dmax`` stages, ``n_models``
    models on ``n_engines`` engines."""
    if min(n_nodes, dmax, n_models, n_engines, lanes) < 1:
        raise ValueError("every shape must be >= 1")
    ops = float(lanes) * n_nodes * (OPS_PER_NODE_MODEL * n_models
                                    + OPS_PER_NODE)
    node_bytes = 4.0 * n_nodes * (NODE_COLUMNS + n_models + dmax)
    lane_bytes = 4.0 * lanes * (LANE_SCALARS + n_engines + n_models)
    return ops, node_bytes + lane_bytes


def peak(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; unknown kinds raise."""
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


def roofline_share(ops: float, nbytes: float, seconds: float,
                   device_kind: str) -> tuple[float, str]:
    """(percent of the roofline, which bound sets it) of work that took
    ``seconds`` of device time."""
    if not seconds > 0:
        raise ValueError("seconds must be > 0")
    pk = peak(device_kind)
    t_ops, t_mem = ops / pk["flops"], nbytes / pk["bytes_per_s"]
    bound = "compute" if t_ops >= t_mem else "memory"
    return 100.0 * max(t_ops, t_mem) / seconds, bound
