"""JAX/TPU-native batched trie controller (DESIGN.md §2.1).

The paper's controller is a per-request CPU DFS (Table 3).  At fleet scale,
thousands of in-flight requests replan after every stage; we therefore
express the re-rooted constrained search as fixed-shape masked reductions
over the structure-of-arrays trie:

- descendants of the realized prefix u are the preorder interval
  [u, u + subtree_size[u])  -> two vectorized comparisons;
- budget feasibility and the accuracy floor are elementwise masks;
- the paper's monotone pruning becomes algebraic masking (same optimum,
  data-parallel instead of search-order dependent);
- tie-breaking is an exact multi-pass lexicographic argmin (NOT an
  epsilon-weighted composite key, whose sub-float32-resolution epsilon
  terms silently collapse ties) so the device planner picks the *same*
  node as the host `select_path` — the property `repro.core.fleet` relies
  on for batched-vs-sequential equivalence;
- `path_models` doubles as a device-side *first-step table*: the next model
  on the path u -> target is `path_models[target, depth[u]]`.

The replan itself dispatches through `repro.kernels.ops.trie_plan`
(ops.py-style ``use_pallas``/variant switch):

- "fused" (default) — the XLA mirror (`kernels/xla_trie.py`):
  per-request lexicographic minima in one tile over the whole trie,
  cumulative engine delay as a path-counts matmul, first-step gather fused
  into the tournament — no (N, Dmax) intermediate, no full-array min-pass;
- "pallas" — the fused Pallas kernel (`kernels/trie_plan.py`), the same
  tile math on a (node tiles x batch lanes) grid with the trie SoA tiles
  VMEM-resident (``interpret=True`` on CPU, compiled on TPU);
- "dense" — the pre-fusion reference (`kernels/ref.fleet_plan`), kept as
  the oracle and as the baseline `benchmarks/table3_overhead.py` measures.

All variants pick the identical node.  The default comes from the
``REPRO_PLAN_VARIANT`` env var (``fused`` unless overridden).

For the event-driven runtime, `make_resident_planner` additionally keeps
the per-slot control state (prefix node, elapsed latency/cost) *resident on
the device* across events: updates for the few slots an event touched are
scattered into donated buffers, so a replan sends only those update lanes
plus one (E,) delay row host->device instead of round-tripping the full
capacity-sized slot arrays every call.

`benchmarks/table3_overhead.py` measures per-replan latency of this path;
`benchmarks/fleet_throughput.py` measures the full fleet step.
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.controller import Objective
from repro.core.trie import Trie, TrieAnnotations
from repro.kernels import ops as kernel_ops

_BIG = 1e30

PLAN_VARIANTS = kernel_ops.TRIE_PLAN_VARIANTS


def default_plan_variant() -> str:
    """Dispatch variant used when callers pass ``variant=None``."""
    v = os.environ.get("REPRO_PLAN_VARIANT", "fused")
    if v not in PLAN_VARIANTS:
        raise ValueError(f"REPRO_PLAN_VARIANT={v!r}: expected one of "
                         f"{PLAN_VARIANTS}")
    return v


def _resolve_variant(variant: str | None) -> str:
    if variant is None:
        return default_plan_variant()
    if variant not in PLAN_VARIANTS:
        raise ValueError(f"unknown plan variant {variant!r}: {PLAN_VARIANTS}")
    return variant


def trie_engines(template) -> list[str]:
    """Canonical (sorted) engine order used for delay vectors everywhere a
    dense per-engine array stands in for the controller's delta_e dict.

    The delay row's semantics are source-agnostic: under the scalar
    `FleetLoadModel` each entry is ``(slowdown - 1) * mean_service_s``;
    under the token calendar (`TokenWorkModel`, ISSUE 10) the slowdown is
    the continuous-batching decode-step ratio ``(n/b) * (step(b)/step(1))``
    at the engine's live sequence count — the planner consumes both
    identically as projected queueing seconds per stage."""
    return sorted({m.engine for m in template.models})


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TrieDevice:
    """Trie + annotations as device arrays (immutable during serving).

    ``path_counts[u, m]`` is the multiplicity of model m on the root->u
    path: the fused planner's cumulative engine delay is one
    ``path_counts @ per_model_delays`` contraction instead of the dense
    (N, Dmax) gather+sum.  ``n_engines`` is static aux data computed once
    at build time — reading it never syncs a device array to the host.
    """

    terminal: jnp.ndarray         # (N,) float32 0/1
    depth: jnp.ndarray            # (N,) float32
    acc: jnp.ndarray              # (N,)
    cost: jnp.ndarray             # (N,)
    lat: jnp.ndarray              # (N,)
    subtree_size: jnp.ndarray     # (N,) int32
    path_models: jnp.ndarray      # (N, Dmax) int32, -1 padded
    path_counts: jnp.ndarray      # (N, M) float32 path multiplicities
    engine_of_model: jnp.ndarray  # (M,) int32
    n_engines: int = 0            # static aux (no device sync on access)

    # annotation-version bookkeeping (online estimator refresh).  Plain
    # class attributes, NOT dataclass fields: they must stay out of both
    # the pytree leaves (a structure change would break every compiled
    # program's operand layout) and the static aux data (a per-version
    # aux would re-trace on every swap — the opposite of the zero-retrace
    # contract).  Instances published by `TrieAnnotator.publish` override
    # them per object.
    version = 0           # 0 = unversioned (built outside the annotator)
    superseded_by = None  # version that donated this device's annotations

    def check_live(self) -> None:
        """Raise a descriptive error when this device's annotation
        buffers were donated by a newer published version.

        Mirrors `ResidentPlanner._check_live`/`reset()`: publishing
        version N+1 via `repro.core.estimators.TrieAnnotator.publish`
        donates (deletes) version N's acc/cost/lat buffers, so a stale
        holder fails here with the version API spelled out instead of
        hitting the runtime's opaque deleted-array error mid-plan."""
        for name in ("acc", "cost", "lat"):
            buf = getattr(self, name)
            try:
                dead = buf.is_deleted()
            except AttributeError:  # array type without deletion tracking
                return
            if dead:
                raise RuntimeError(
                    f"TrieDevice annotation column {name!r} (version "
                    f"{self.version}) reads a donated buffer: this device "
                    f"was superseded by version {self.superseded_by} when "
                    "the online annotator published a refresh.  Use the "
                    "TrieDevice returned by TrieAnnotator.publish() — and "
                    "hand it to ResidentPlanner.swap_device(new_td) — "
                    "instead of a superseded version.")

    def supersede(self, new_version: int) -> None:
        """Donate this device's annotation buffers to the version that
        replaced it: the acc/cost/lat storage is deleted on device, so
        any stale reader fails loudly through `check_live`.  The
        structural columns (trie topology) are shared across versions and
        stay live."""
        self.superseded_by = new_version
        for name in ("acc", "cost", "lat"):
            buf = getattr(self, name)
            delete = getattr(buf, "delete", None)
            if callable(delete):
                try:
                    delete()
                except Exception:
                    pass  # already deleted / backend without donation

    def tree_flatten(self):
        """Pytree protocol: device arrays are leaves, ``n_engines`` is
        static aux data (it shapes compiled programs)."""
        return (
            (self.terminal, self.depth, self.acc, self.cost, self.lat,
             self.subtree_size, self.path_models, self.path_counts,
             self.engine_of_model),
            self.n_engines,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Pytree protocol inverse of `tree_flatten`."""
        return cls(*children, n_engines=aux)

    @staticmethod
    def build(trie: Trie, ann: TrieAnnotations,
              restrict_nodes: np.ndarray | None = None) -> "TrieDevice":
        """Stage the trie + annotations into device-resident columns
        (float32), optionally restricting the terminal set to
        ``restrict_nodes`` — one upload reused by every jitted plan."""
        terminal = trie.terminal.copy()
        if restrict_nodes is not None:
            keep = np.zeros(trie.n_nodes, dtype=bool)
            keep[restrict_nodes] = True
            terminal &= keep
        engines = trie_engines(trie.template)
        eidx = {e: i for i, e in enumerate(engines)}
        eom = np.array([eidx[m.engine] for m in trie.template.models],
                       dtype=np.int32)
        n = trie.n_nodes
        dmax = trie.template.max_depth
        # parent-pointer fill, one vectorized pass per depth level: each
        # level copies its parents' path prefixes/counts and appends its own
        # edge (the per-node `trie.path(u)` walk is O(N * Dmax) in Python
        # and dominated cold-start for large tries)
        pm = np.full((n, dmax), -1, dtype=np.int32)
        counts = np.zeros((n, trie.template.n_models), dtype=np.float32)
        for d in range(1, int(trie.depth.max()) + 1):
            nodes = np.nonzero(trie.depth == d)[0]
            par = trie.parent[nodes]
            if d > 1:
                pm[nodes, : d - 1] = pm[par, : d - 1]
                counts[nodes] = counts[par]
            pm[nodes, d - 1] = trie.model[nodes]
            counts[nodes, trie.model[nodes]] += 1.0
        return TrieDevice(
            terminal=jnp.asarray(terminal, jnp.float32),
            depth=jnp.asarray(trie.depth, jnp.float32),
            acc=jnp.asarray(ann.acc, jnp.float32),
            cost=jnp.asarray(ann.cost, jnp.float32),
            lat=jnp.asarray(ann.lat, jnp.float32),
            subtree_size=jnp.asarray(trie.subtree_size, jnp.int32),
            path_models=jnp.asarray(pm, jnp.int32),
            path_counts=jnp.asarray(counts, jnp.float32),
            engine_of_model=jnp.asarray(eom, jnp.int32),
            n_engines=int(eom.max()) + 1,
        )


def _dispatch_plan(td: TrieDevice, prefixes, elapsed_lat, elapsed_cost,
                   engine_delays, blocked, acc_floor, cost_cap, lat_cap,
                   *, kind, variant):
    return kernel_ops.trie_plan(
        td.terminal, td.depth, td.acc, td.cost, td.lat, td.subtree_size,
        td.path_models, td.path_counts, td.engine_of_model,
        prefixes, elapsed_lat, elapsed_cost, engine_delays,
        acc_floor, cost_cap, lat_cap, kind=kind, variant=variant,
        blocked_depth=blocked)


@partial(jax.jit, static_argnames=("kind", "variant"))
def _plan_shared_delays(td, prefixes, elapsed_lat, elapsed_cost,
                        engine_delays, blocked, acc_floor, cost_cap,
                        lat_cap, *, kind, variant):
    delays = jnp.broadcast_to(
        engine_delays[None, :], (prefixes.shape[0], engine_delays.shape[0]))
    tgt, _ = _dispatch_plan(td, prefixes, elapsed_lat, elapsed_cost, delays,
                            blocked, acc_floor, cost_cap, lat_cap,
                            kind=kind, variant=variant)
    return tgt


@partial(jax.jit, static_argnames=("kind", "variant"))
def _fleet_step(td, prefixes, elapsed_lat, elapsed_cost, engine_delays,
                blocked, acc_floor, cost_cap, lat_cap, *, kind, variant):
    """One lockstep replan for a whole fleet: targets AND first steps.

    `engine_delays` is (B, E) — per-request live delay vectors, so a
    load-aware fleet can charge each request the congestion it would
    actually see.  The "next model on the path u -> target" lookup is a
    single gather into the dense first-step table: `path_models[v, d]` is
    the model chosen at invocation position d on the root->v path, and the
    next step from a depth-d prefix toward v is exactly that entry (fused
    into the tiled pass under the "fused"/"pallas" variants).

    ``blocked`` is the (N,) engine-availability mask rendered as a node
    column (`blocked_depth`; all-zeros = every engine up) — a traced
    operand like the annotation columns, so outage/recovery mask flips
    are pure value changes with ZERO new compiled programs.
    """
    return _dispatch_plan(td, prefixes, elapsed_lat, elapsed_cost,
                          engine_delays, blocked, acc_floor, cost_cap,
                          lat_cap, kind=kind, variant=variant)


# ----------------------------------------------------------------------
# device-resident slot state for the event-driven runtime
# ----------------------------------------------------------------------
_UPDATE_WIDTH = 8  # slots per scatter call; events touch few lanes each


@partial(jax.jit, donate_argnums=(0, 1, 2))
def _apply_slot_updates(u, el, ec, idx, new_u, new_el, new_ec):
    """Scatter one fixed-width batch of per-slot updates into the donated
    device-resident state (padding lanes use idx == capacity -> dropped)."""
    u = u.at[idx].set(new_u, mode="drop")
    el = el.at[idx].set(new_el, mode="drop")
    ec = ec.at[idx].set(new_ec, mode="drop")
    return u, el, ec


@partial(jax.jit, static_argnames=("kind", "variant"))
def _resident_plan(td, u, el, ec, delay_row, blocked, acc_floor, cost_cap,
                   lat_cap, *, kind, variant):
    """Replan over the device-resident slot arrays with one shared (E,)
    delay row and one shared (N,) availability mask (the only per-replan
    host->device tensors)."""
    delays = jnp.broadcast_to(
        delay_row[None, :], (u.shape[0], delay_row.shape[0]))
    return _dispatch_plan(td, u, el, ec, delays, blocked, acc_floor,
                          cost_cap, lat_cap, kind=kind, variant=variant)


# ----------------------------------------------------------------------
# lane-sharded resident programs (multi-device control plane)
# ----------------------------------------------------------------------
# One compiled program per (mesh, ...) key, registered here so
# `fleet_planner_cache_size` keeps covering every planner program the
# process traced (the no-retrace guards sum this dict too).
_SHARDED_JITS: dict[tuple, object] = {}


def _mesh_key(mesh) -> tuple:
    return tuple(d.id for d in np.asarray(mesh.devices).flat)


def _sharded_scatter(mesh, n_cols: int):
    """shard_map'd masked scatter into ``n_cols`` lane-sharded columns.

    Each device owns one contiguous lane block [base, base + per): global
    update indices outside the local block are remapped to the
    out-of-range local index ``per``, which ``mode="drop"`` discards — so
    every device applies the same replicated update batch to its own
    block with ZERO collectives."""
    key = ("scatter", n_cols, _mesh_key(mesh))
    if key in _SHARDED_JITS:
        return _SHARDED_JITS[key]
    from jax.sharding import PartitionSpec

    from repro.dist.sharding import LANE_AXIS, lane_spec
    lane, rep = lane_spec(), PartitionSpec()

    def scatter(cols, idx, vals):
        per = cols[0].shape[0]
        base = jax.lax.axis_index(LANE_AXIS) * per
        loc = jnp.where((idx >= base) & (idx < base + per),
                        idx - base, per)
        return tuple(c.at[loc].set(v, mode="drop")
                     for c, v in zip(cols, vals))

    fn = jax.jit(jax.shard_map(scatter, mesh=mesh,
                               in_specs=(lane, rep, rep),
                               out_specs=(lane,) * n_cols, check_vma=False),
                 donate_argnums=(0,))
    _SHARDED_JITS[key] = fn
    return fn


def _sharded_plan(mesh, kind: str, variant: str):
    """shard_map'd lane-local replan: each device plans only its own lane
    block (the planner is lane-independent, so block results are bitwise
    the lanes of a capacity-wide call) against the replicated trie SoA and
    the shared replicated (E,) delay row.  Zero collectives."""
    key = ("plan", kind, variant, _mesh_key(mesh))
    if key in _SHARDED_JITS:
        return _SHARDED_JITS[key]
    from jax.sharding import PartitionSpec

    from repro.dist.sharding import lane_spec
    lane, rep = lane_spec(), PartitionSpec()

    def plan(td, u, el, ec, delay_row, blocked, acc_floor, cost_cap,
             lat_cap):
        delays = jnp.broadcast_to(
            delay_row[None, :], (u.shape[0], delay_row.shape[0]))
        return _dispatch_plan(td, u, el, ec, delays, blocked, acc_floor,
                              cost_cap, lat_cap, kind=kind, variant=variant)

    fn = jax.jit(jax.shard_map(
        plan, mesh=mesh,
        in_specs=(rep, lane, lane, lane, rep, rep, rep, rep, rep),
        out_specs=(lane, lane), check_vma=False))
    _SHARDED_JITS[key] = fn
    return fn


def _sharded_plan_coupled(mesh, kind: str, variant: str):
    """Load-coupled sharded replan: the per-engine delay row is derived
    from the *resident* lane->engine occupancy columns, so each device
    contributes its own lanes' partial occupancy row and exactly ONE
    `psum` per replan round merges them — the only cross-shard coupling
    in the sharded control plane (the delay row every lane's feasibility
    test reads).  The slowdown model mirrors
    ``FleetLoadModel.delays``: ``(max(1, (occ + 1) / conc) - 1) * ms``
    on engines that have a load model (``hasm``)."""
    key = ("plan_coupled", kind, variant, _mesh_key(mesh))
    if key in _SHARDED_JITS:
        return _SHARDED_JITS[key]
    from jax.sharding import PartitionSpec

    from repro.dist.sharding import LANE_AXIS, lane_spec
    lane, rep = lane_spec(), PartitionSpec()

    def plan(td, u, el, ec, park, w, blocked, conc, ms, hasm,
             acc_floor, cost_cap, lat_cap):
        E = conc.shape[0]
        act = park >= 0
        parkc = jnp.where(act, jnp.clip(park, 0, E - 1), E)
        occ_part = jnp.zeros(E + 1, w.dtype).at[parkc].add(
            jnp.where(act, w, 0.0))[:E]
        occ = jax.lax.psum(occ_part, LANE_AXIS)  # the one collective
        row = jnp.where(
            hasm, (jnp.maximum(1.0, (occ + 1.0) / conc) - 1.0) * ms,
            0.0).astype(jnp.float32)
        delays = jnp.broadcast_to(row[None, :], (u.shape[0], E))
        tgt, nxt = _dispatch_plan(td, u, el, ec, delays, blocked,
                                  acc_floor, cost_cap, lat_cap, kind=kind,
                                  variant=variant)
        return tgt, nxt, row

    fn = jax.jit(jax.shard_map(
        plan, mesh=mesh,
        in_specs=(rep, lane, lane, lane, lane, lane, rep,
                  rep, rep, rep, rep, rep, rep),
        out_specs=(lane, lane, rep), check_vma=False))
    _SHARDED_JITS[key] = fn
    return fn


class ResidentPlanner:
    """Fleet replanner whose slot state lives on the device across events.

    The event-driven runtime (`repro.core.events`) holds the authoritative
    per-slot control state on the host (policies and the executor need it),
    and mirrors the lanes each event touches into donated device buffers
    via `update` — fixed-width scatters, so the program set never retraces.
    `replan` then runs the fused planner over the resident arrays without
    re-uploading them: per replan the wire carries only the update lanes
    and one (E,) delay row in, and the (C,) target/next-model lanes out.

    Slots not updated since their last replan may hold stale values — the
    event loop only reads lanes it just updated (exactly the lanes whose
    state changed), so staleness is never observable.

    Per-slot deadlines (priority classes) ride on the existing lanes with
    ZERO new compiled programs: ``lat_cap`` overrides the single traced
    latency-cap scalar with the *largest* class deadline, and the caller
    shifts each lane's elapsed latency by ``lat_cap - class_deadline``
    (``-inf`` for deadline-free classes) so the kernel's ``d_lat <=
    lat_cap - elapsed`` feasibility test evaluates every lane against its
    own class deadline.  Scalars are traced operands, so changing the cap
    value never re-traces.

    ``mesh`` (a 1-D `repro.dist.sharding.lane_mesh`) shards the slot
    columns over the lane axis: capacity is padded to a device multiple
    (`lane_counts`; pad lanes are dead), updates become collective-free
    masked block scatters, and the replan runs lane-locally per device —
    bitwise the same lanes as the single-device call, since the planner
    is lane-independent and the trie SoA is replicated.  `replan_coupled`
    additionally derives the shared delay row from resident lane->engine
    occupancy columns with exactly one `psum` per replan round (the only
    cross-shard coupling).

    The slot buffers are DONATED to the update scatter: a host-side
    exception that interrupts a call (or any external consumer of the
    donated arrays) leaves them invalidated, which `update`/`replan`
    detect and report as a `RuntimeError` naming `reset` instead of the
    runtime's opaque deleted-array error.  `reset` rematerializes zeroed
    buffers; the host re-mirrors every lane it reads before reading it
    (the staleness contract above), so serving resumes correctly.
    """

    def __init__(self, td: TrieDevice, obj: Objective, capacity: int,
                 variant: str | None = None, lat_cap: float | None = None,
                 mesh=None):
        self.capacity = int(capacity)
        self.variant = _resolve_variant(variant)
        self._td = td
        self._kind = obj.kind
        # all-engines-up availability mask: the (N,) blocked_depth operand
        # every replan is fed when the caller passes no fault mask — a real
        # array (not None) so fault transitions are pure value changes
        self._bd0 = jnp.zeros_like(td.depth)
        if lat_cap is not None:
            obj = dataclasses.replace(obj, lat_cap=float(lat_cap))
        self._scalars = _objective_scalars(obj)
        self.mesh = mesh
        if mesh is None:
            self._n_lanes = self.capacity
            self._sharding = None
        else:
            from repro.dist.sharding import lane_counts, lane_spec
            self._n_lanes, _ = lane_counts(self.capacity, mesh)
            self._sharding = jax.sharding.NamedSharding(mesh, lane_spec())
            self._scatter3 = _sharded_scatter(mesh, 3)
            self._scatter2 = _sharded_scatter(mesh, 2)
            self._plan_fn = _sharded_plan(mesh, self._kind, self.variant)
            self._plan_coupled_fn = _sharded_plan_coupled(
                mesh, self._kind, self.variant)
        self._materialize()
        # two fixed scatter widths: a small one for the few lanes a steady-
        # state event touches, and a capacity-wide one so an admission burst
        # is a single dispatch instead of ceil(C / width) sequential calls
        self._w_small = min(_UPDATE_WIDTH, self.capacity)
        # warm both programs now: the no-retrace guards snapshot the compile
        # counter after the first replan, and the burst width must not trace
        # mid-sweep the first time a full cohort lands in one event
        for w in {self._w_small, self.capacity}:
            self._scatter(np.full(w, self._n_lanes, dtype=np.int32),
                          np.zeros(w, dtype=np.int32),
                          np.zeros(w, dtype=np.float32),
                          np.zeros(w, dtype=np.float32))

    def _materialize(self) -> None:
        def zeros(dtype, fill=None):
            a = (jnp.zeros((self._n_lanes,), dtype) if fill is None
                 else jnp.full((self._n_lanes,), fill, dtype))
            return a if self._sharding is None \
                else jax.device_put(a, self._sharding)

        self._u = zeros(jnp.int32)
        self._el = zeros(jnp.float32)
        self._ec = zeros(jnp.float32)
        # lane->engine occupancy columns for `replan_coupled` (sharded
        # mode only; -1 = lane holds no running stage)
        self._park = None if self.mesh is None else zeros(jnp.int32, -1)
        self._w = None if self.mesh is None else zeros(jnp.float32)

    def _live_buffers(self):
        bufs = [self._u, self._el, self._ec]
        if self._park is not None:
            bufs += [self._park, self._w]
        return bufs

    def _check_live(self) -> None:
        self._td.check_live()  # superseded annotation versions fail loudly
        try:
            dead = any(b.is_deleted() for b in self._live_buffers())
        except AttributeError:  # array type without deletion tracking
            return
        if dead:
            raise RuntimeError(
                "ResidentPlanner's device-resident slot buffers have been "
                "invalidated: a previous update donated them and did not "
                "complete (e.g. a host-side exception between events), so "
                "the runtime deleted the storage.  Call reset() to "
                "rematerialize zeroed buffers — the event loop re-mirrors "
                "every lane it reads before reading it, so serving resumes "
                "correctly — or construct a fresh planner.")

    def reset(self) -> None:
        """Rematerialize zeroed resident buffers after donation
        invalidation (see `_check_live`).  Compiled programs are
        unaffected — only the storage is rebuilt."""
        self._materialize()

    def _scatter(self, idx, nu, nel, nec) -> None:
        with warnings.catch_warnings():
            # donation falls back to copies on backends without support
            # (e.g. some CPU jaxlibs) — harmless, don't spam every event
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            if self.mesh is None:
                self._u, self._el, self._ec = _apply_slot_updates(
                    self._u, self._el, self._ec, idx, nu, nel, nec)
            else:
                self._u, self._el, self._ec = self._scatter3(
                    (self._u, self._el, self._ec), idx, (nu, nel, nec))

    def _pad(self, slots, *cols):
        """Fixed-width update batch: pad index ``n_lanes`` lies outside
        every lane block, so pad entries are dropped by the scatter."""
        n = slots.shape[0]
        w = self._w_small if n <= self._w_small else self.capacity
        idx = np.full(w, self._n_lanes, dtype=np.int32)
        idx[:n] = slots
        out = [idx]
        for c in cols:
            buf = np.zeros(w, dtype=c.dtype)
            buf[:n] = c
            out.append(buf)
        return out

    @property
    def device_version(self) -> int:
        """Annotation version of the trie device currently planned
        against (0 when the device was built outside the annotator)."""
        return self._td.version

    @property
    def scalars(self):
        """The traced objective-scalar operands ``(acc_floor, cost_cap,
        lat_cap)`` (float32) every planner program is fed — under
        per-class deadline serving ``lat_cap`` is the largest finite
        class cap.  Host-side guards (the exploration lane's float32
        feasibility check in `repro.core.events`) read these to
        reproduce the device arithmetic exactly."""
        return self._scalars

    def swap_device(self, td: TrieDevice) -> TrieDevice:
        """Swap in a re-annotated `TrieDevice` (online estimator refresh).

        The annotation columns are *traced operands* to every planner
        program, so as long as the new device has the identical leaf
        structure (same trie topology, same shapes/dtypes) the swap is a
        pure buffer substitution: ZERO new compiled programs
        (`fleet_planner_cache_size` stays flat across swaps — pinned by
        tests/test_golden.py).  Structure drift raises instead of
        silently re-tracing.  Returns the device swapped out (usually
        already superseded — its annotation buffers donated — by
        `TrieAnnotator.publish`)."""
        old_leaves, old_aux = self._td.tree_flatten()
        new_leaves, new_aux = td.tree_flatten()
        old_sig = [(a.shape, a.dtype) for a in old_leaves]
        new_sig = [(a.shape, a.dtype) for a in new_leaves]
        if old_sig != new_sig or old_aux != new_aux:
            raise ValueError(
                "swap_device requires a TrieDevice with the identical "
                "array structure (same trie, annotations only) — a "
                f"structure change would re-trace. got {new_sig} / aux "
                f"{new_aux}, expected {old_sig} / aux {old_aux}")
        td.check_live()
        old = self._td
        self._td = td
        return old

    def update(self, slots, u_vals, el_vals, ec_vals) -> None:
        """Mirror host-side state for ``slots`` into the resident buffers."""
        self._check_live()
        idx, nu, nel, nec = self._pad(
            np.asarray(slots, dtype=np.int32),
            np.asarray(u_vals, dtype=np.int32),
            np.asarray(el_vals, dtype=np.float32),
            np.asarray(ec_vals, dtype=np.float32))
        self._scatter(idx, nu, nel, nec)

    def update_loads(self, slots, engine_ids, weights) -> None:
        """Mirror lane->engine occupancy (engine index or -1, weighted
        share) for ``slots`` into the resident load columns that
        `replan_coupled` derives the delay row from (sharded mode)."""
        if self.mesh is None:
            raise RuntimeError("update_loads requires a lane mesh "
                               "(make_resident_planner(..., mesh=))")
        self._check_live()
        idx, pk, wv = self._pad(
            np.asarray(slots, dtype=np.int32),
            np.asarray(engine_ids, dtype=np.int32),
            np.asarray(weights, dtype=np.float32))
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            self._park, self._w = self._scatter2(
                (self._park, self._w), idx, (pk, wv))

    def replan(self, delay_row,
               blocked=None) -> tuple[np.ndarray, np.ndarray]:
        """One fused replan over all capacity lanes; returns host
        (targets, next_models).  ``delay_row`` is the (E,) shared delta_e
        vector for this instant; ``blocked`` is the (N,) ``blocked_depth``
        availability mask (None = every engine up) — a traced operand, so
        outage/recovery flips never retrace."""
        self._check_live()
        row = np.asarray(delay_row, dtype=np.float32)
        bd = self._bd0 if blocked is None \
            else jnp.asarray(np.asarray(blocked, dtype=np.float32))
        if self.mesh is None:
            tgt, nxt = _resident_plan(
                self._td, self._u, self._el, self._ec, row, bd,
                *self._scalars, kind=self._kind, variant=self.variant)
        else:
            tgt, nxt = self._plan_fn(
                self._td, self._u, self._el, self._ec, row, bd,
                *self._scalars)
        C = self.capacity
        return np.asarray(tgt)[:C], np.asarray(nxt)[:C]

    def replan_coupled(self, conc, ms, hasm, blocked=None):
        """Load-coupled sharded replan: derives the per-engine delay row
        from the resident occupancy columns (`update_loads`) with exactly
        one `psum`, then plans every lane against it.  ``conc``/``ms``/
        ``hasm`` are the (E,) `FleetLoadModel` parameter rows (traced
        operands — value changes never retrace).  Returns host
        ``(targets, next_models, delay_row)``."""
        if self.mesh is None:
            raise RuntimeError("replan_coupled requires a lane mesh "
                               "(make_resident_planner(..., mesh=))")
        self._check_live()
        bd = self._bd0 if blocked is None \
            else jnp.asarray(np.asarray(blocked, dtype=np.float32))
        tgt, nxt, row = self._plan_coupled_fn(
            self._td, self._u, self._el, self._ec, self._park, self._w,
            bd, np.asarray(conc, dtype=np.float32),
            np.asarray(ms, dtype=np.float32),
            np.asarray(hasm, dtype=bool), *self._scalars)
        C = self.capacity
        return np.asarray(tgt)[:C], np.asarray(nxt)[:C], np.asarray(row)


def traced_fleet_plan(td: TrieDevice, prefixes, elapsed_lat, elapsed_cost,
                      delay_row, scalars, *, kind: str, variant: str,
                      blocked=None):
    """Planner call for use INSIDE an already-traced computation.

    The compiled event engine (`repro.core.events_compiled`) invokes the
    replan from within its jitted epoch step, so it needs the planner's
    math without `_resident_plan`'s own jit wrapper (nested jit would be a
    no-op but obscures the single-program property the engine asserts on).
    This is exactly `_resident_plan`'s body: one shared (E,) float32 delay
    row broadcast across the capacity lanes, then the variant-dispatched
    kernel.  All operands must already carry the kernel's dtypes (int32
    prefixes, float32 elapsed/cost/delays) — inside an
    ``jax.enable_x64`` scope the kernel arithmetic stays
    float32 end-to-end, bit-matching the host planner's programs.

    ``blocked`` is the (N,) float32 ``blocked_depth`` availability mask
    (None = every engine up); inside the compiled engine it is an epoch
    state column, so mask flips at fault boundaries are traced value
    changes, not new programs.

    Returns ``(targets, next_models)`` as traced int32 lanes.
    """
    if blocked is None:
        blocked = jnp.zeros_like(td.depth)
    delays = jnp.broadcast_to(
        delay_row[None, :], (prefixes.shape[0], delay_row.shape[0]))
    return _dispatch_plan(td, prefixes, elapsed_lat, elapsed_cost, delays,
                          blocked, *scalars, kind=kind, variant=variant)


def objective_scalars(obj: Objective):
    """Public alias of the planner's traced objective scalars
    ``(acc_floor, cost_cap, lat_cap)`` (float32; None caps become the
    planner's BIG sentinel) — the operand bundle `traced_fleet_plan` and
    the resident planner share."""
    return _objective_scalars(obj)


def make_resident_planner(td: TrieDevice, obj: Objective, capacity: int,
                          variant: str | None = None,
                          lat_cap: float | None = None,
                          mesh=None) -> ResidentPlanner:
    """Device-resident fleet replanner for the event-driven runtime.

    ``lat_cap`` overrides the objective's latency cap with the effective
    (largest) per-class deadline so priority classes can express per-slot
    deadlines through elapsed-latency shifts — see `ResidentPlanner`.
    ``mesh`` (from `repro.dist.sharding.lane_mesh`) shards the slot lanes
    across devices — see `ResidentPlanner` for the partitioning and the
    single-`psum` load coupling."""
    return ResidentPlanner(td, obj, capacity, variant, lat_cap, mesh)


def fleet_planner_cache_size() -> int:
    """Total compiled specializations across the planner's jitted programs,
    or -1 when the JAX runtime doesn't expose the counter.

    Covers the fleet-step program (one entry per trie shape x batch size x
    objective kind x variant), the shared-delay batched form, the
    device-resident pair (slot-update scatter + resident replan), and the
    lane-sharded programs (one scatter/plan set per lane mesh).  The
    event-driven runtime pins its planner batch at the slot capacity and
    its scatter width at `_UPDATE_WIDTH` precisely so this stays flat while
    the number of in-flight requests fluctuates — tests and
    `benchmarks/open_arrival.py` assert no growth across a whole
    arrival-rate sweep."""
    total, found = 0, False
    for fn in (_fleet_step, _plan_shared_delays, _resident_plan,
               _apply_slot_updates, *_SHARDED_JITS.values()):
        try:
            total += int(fn._cache_size())
            found = True
        except Exception:
            pass
    return total if found else -1


def _objective_scalars(obj: Objective):
    acc_floor = jnp.float32(
        (obj.acc_floor if obj.acc_floor is not None else -1.0) + obj.acc_margin
    )
    cost_cap = jnp.float32(obj.cost_cap if obj.cost_cap is not None else _BIG)
    lat_cap = jnp.float32(obj.lat_cap if obj.lat_cap is not None else _BIG)
    return acc_floor, cost_cap, lat_cap


def make_batched_planner(td: TrieDevice, obj: Objective,
                         variant: str | None = None):
    """Returns plan(prefixes, elapsed_lat, elapsed_cost, engine_delays) ->
    best terminating node per request (int32, -1 infeasible), batched over
    the request batch with one shared (E,) engine-delay vector.

    The underlying jitted program is module-level, so planners built for
    different objectives (or rebuilt per cohort) share one compilation per
    (trie shape, batch size, objective kind, variant) — objective scalars
    are traced operands, not compile-time constants."""
    scalars = _objective_scalars(obj)
    variant = _resolve_variant(variant)
    bd0 = jnp.zeros_like(td.depth)

    def plan(prefixes, elapsed_lat, elapsed_cost, engine_delays,
             blocked=None):
        return _plan_shared_delays(
            td, prefixes, elapsed_lat, elapsed_cost, engine_delays,
            bd0 if blocked is None else blocked,
            *scalars, kind=obj.kind, variant=variant)

    return plan


def make_fleet_planner(td: TrieDevice, obj: Objective,
                       variant: str | None = None):
    """Returns step(prefixes, elapsed_lat, elapsed_cost, engine_delays) ->
    (targets, next_models), the fleet runtime's one-call-per-step replanner.
    `engine_delays` has shape (B, E): one live delay vector per request."""
    scalars = _objective_scalars(obj)
    variant = _resolve_variant(variant)
    bd0 = jnp.zeros_like(td.depth)

    def step(prefixes, elapsed_lat, elapsed_cost, engine_delays,
             blocked=None):
        return _fleet_step(
            td, prefixes, elapsed_lat, elapsed_cost, engine_delays,
            bd0 if blocked is None else blocked,
            *scalars, kind=obj.kind, variant=variant)

    return step


def make_admission_probe(td: TrieDevice, obj: Objective,
                         variant: str | None = None):
    """Batched admission-feasibility probe for the load-shedding layer.

    Returns feasible(prefixes, elapsed_lat, elapsed_cost, engine_delays) ->
    (B,) bool: True where at least one terminating plan in the request's
    remaining subtrie fits its remaining budgets under the live per-engine
    delays.  This is exactly ``targets >= 0`` of the fleet-step program —
    the probe invokes the SAME module-level jitted `_fleet_step` with the
    same operand shapes as `make_fleet_planner`, so consulting it at
    arrival/admission time adds ZERO compiled specializations
    (`fleet_planner_cache_size` must not grow; `benchmarks/admission.py`
    and tests/test_admission.py assert this).  The event-driven runtime
    gets the same answer for free by loading probe rows into free planner
    lanes; this standalone wrapper serves external admission gates."""
    scalars = _objective_scalars(obj)
    variant = _resolve_variant(variant)
    bd0 = jnp.zeros_like(td.depth)

    def feasible(prefixes, elapsed_lat, elapsed_cost, engine_delays,
                 blocked=None):
        # canonicalize dtypes BEFORE the jit boundary: a float64 operand
        # (numpy's default) would otherwise trace a new specialization and
        # void the zero-compile guarantee this probe exists to provide
        tgt, _ = _fleet_step(
            td,
            np.asarray(prefixes, dtype=np.int32),
            np.asarray(elapsed_lat, dtype=np.float32),
            np.asarray(elapsed_cost, dtype=np.float32),
            np.asarray(engine_delays, dtype=np.float32),
            bd0 if blocked is None
            else jnp.asarray(np.asarray(blocked, dtype=np.float32)),
            *scalars, kind=obj.kind, variant=variant)
        return np.asarray(tgt) >= 0

    return feasible


def next_model_for(trie: Trie, u: int, target: int) -> int:
    """First model on the path u -> target (host-side, O(depth))."""
    if target < 0 or target == u:
        return -1
    chain = trie.ancestors(target)
    i = chain.index(u)
    return int(trie.model[chain[i + 1]])
