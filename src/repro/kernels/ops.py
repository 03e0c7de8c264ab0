"""Jit'd kernel wrappers with XLA fallback and recompute-based gradients.

Each op dispatches on ``use_pallas``:
- True  -> the Pallas TPU kernel (``interpret=True`` on CPU, compiled on TPU);
- False -> the pure-jnp reference (`ref.py`) — the path the CPU dry-run
  lowers, and the oracle tests compare against.

Backward passes use `jax.custom_vjp` with the reference implementation
recomputed in the backward (standard flash-attention remat pattern): the
forward enjoys the fused kernel, the backward is mathematically identical
to differentiating the reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _pallas_decode
from repro.kernels.flash_attention import flash_attention as _pallas_flash
from repro.kernels.rmsnorm import rms_norm as _pallas_rmsnorm
from repro.kernels.ssd_scan import ssd_scan as _pallas_ssd
from repro.kernels.trie_plan import DEFAULT_BLOCK_NODES
from repro.kernels.trie_plan import trie_plan_pallas as _pallas_trie_plan
from repro.kernels.xla_flash import decode_attention_xla, flash_attention_xla
from repro.kernels.xla_ssd import ssd_scan_chunked
from repro.kernels.xla_trie import fleet_plan_blocked

# below this many score elements the naive reference is cheaper than the
# blocked path (and small shapes may not tile evenly)
_NAIVE_ATTN_ELEMS = 512 * 512
_NAIVE_SSD_LEN = 256
# the replan sweep's named scope: one of the event engine's four
# (`repro.core.events_compiled.SCOPES`)
PLAN_SCOPE = "vinelm/plan"


def _interpret() -> bool:
    """Pallas mode for the default backend: compiled on TPU, interpret mode
    on CPU (the test lane).  Any other backend raises rather than silently
    running the interpreter."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on TPU or interpreted on CPU; the "
        f"default JAX backend is {backend!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attention_pallas(q, k, v, causal, window):
    return _pallas_flash(q, k, v, causal=causal, window=window,
                         interpret=_interpret())


def _attention_fwd(q, k, v, causal, window):
    return _attention_pallas(q, k, v, causal, window), (q, k, v)


def _attention_bwd(causal, window, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.attention(q_, k_, v_, causal=causal,
                                         window=window), q, k, v)
    return vjp(g)


_attention_pallas.defvjp(_attention_fwd, _attention_bwd)


def attention(q, k, v, *, causal=True, window=0, use_pallas=False):
    """(B,H,Sq,D) x (B,KV,Sk,D)^2 -> (B,H,Sq,D).

    XLA path dispatches to the blocked flash implementation for long
    sequences (O(S) memory, same math); the naive reference covers small
    shapes and serves as the oracle in tests."""
    if use_pallas:
        return _attention_pallas(q, k, v, causal, window)
    Sq, Sk = q.shape[2], k.shape[2]
    if (Sq * Sk > _NAIVE_ATTN_ELEMS and Sq % 512 == 0 and Sk % 512 == 0):
        return flash_attention_xla(q, k, v, causal, window)
    return ref.attention(q, k, v, causal=causal, window=window)


def decode_attention(q, k_cache, v_cache, cache_len, *, window=0,
                     use_pallas=False):
    """(B,H,D) x (B,KV,S,D)^2 -> (B,H,D). Inference-only (no vjp needed).

    Long caches use the blocked online-softmax path (no (B,H,S) score
    buffer); short caches use the naive oracle."""
    if use_pallas:
        return _pallas_decode(q, k_cache, v_cache, cache_len, window=window,
                              interpret=_interpret())
    # NOTE: a blocked K-scan variant (decode_attention_xla) was tried and
    # REFUTED for the sharded dry-run: dynamic block slices over the
    # sequence-sharded cache force per-block all-gathers (435x collective
    # regression), while the naive einsum partitions into sequence-parallel
    # flash-decode under SPMD (EXPERIMENTS.md §Perf).  The Pallas kernel
    # covers the on-chip fusion on real TPUs.
    return ref.decode_attention(q, k_cache, v_cache, cache_len, window=window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd_pallas(x, dt, A, Bm, Cm, chunk):
    return _pallas_ssd(x, dt, A, Bm, Cm, chunk=chunk,
                       interpret=_interpret())


def _ssd_fwd(x, dt, A, Bm, Cm, chunk):
    return _ssd_pallas(x, dt, A, Bm, Cm, chunk), (x, dt, A, Bm, Cm)


def _ssd_bwd(chunk, res, g):
    x, dt, A, Bm, Cm = res
    _, vjp = jax.vjp(lambda *a: ref.ssd_scan(*a, chunk=chunk), x, dt, A, Bm, Cm)
    return vjp(g)


_ssd_pallas.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, A, Bm, Cm, *, chunk=64, use_pallas=False,
             init_state=None, return_state=False):
    """Chunked SSD scan.  Pallas kernel for the stateless full-sequence
    form; XLA path uses the chunk-parallel formulation (associative scan
    over chunks — no sequential time-scan) for long sequences and the
    sequential oracle for short ones."""
    if use_pallas and init_state is None and not return_state:
        return _ssd_pallas(x, dt, A, Bm, Cm, chunk)
    S = x.shape[1]
    if S > _NAIVE_SSD_LEN and S % min(chunk, S) == 0:
        return ssd_scan_chunked(x, dt, A, Bm, Cm, chunk=chunk,
                                init_state=init_state,
                                return_state=return_state)
    return ref.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                        init_state=init_state, return_state=return_state)


def ssd_decode_step(x, dt, A, Bm, Cm, state):
    return ref.ssd_decode_step(x, dt, A, Bm, Cm, state)


def rms_norm(x, scale, eps=1e-6, *, use_pallas=False):
    if use_pallas:
        return _pallas_rmsnorm(x, scale, eps, interpret=_interpret())
    return ref.rms_norm(x, scale, eps)


TRIE_PLAN_VARIANTS = ("dense", "fused", "pallas")


def trie_plan_tiles(n_nodes: int, variant: str) -> int:
    """Node tiles one `trie_plan` sweep of ``variant`` runs over an
    ``n_nodes``-node trie: the Pallas kernel's grid rows (its
    `DEFAULT_BLOCK_NODES`-node tiles, narrowed to the padded trie); the
    fused and dense variants take the whole trie at once."""
    if variant != "pallas":
        return 1
    block = min(DEFAULT_BLOCK_NODES, -(-n_nodes // 128) * 128)
    return -(-n_nodes // block)


def trie_plan(terminal, depth, acc, cost, lat, subtree_size, path_models,
              path_counts, engine_of_model, prefixes, elapsed_lat,
              elapsed_cost, engine_delays, acc_floor, cost_cap, lat_cap,
              *, kind, variant="fused", use_pallas=False,
              blocked_depth=None):
    """Fused fleet replan -> (targets, next_models), both (B,) int32.

    The VineLM control-plane hot path (`controller_jax._fleet_step` routes
    here).  ``variant`` selects the implementation:

    - "pallas" (or ``use_pallas=True``) -> the tiled Pallas kernel
      (``interpret=True`` on CPU, compiled on TPU);
    - "fused"  -> the XLA mirror (same tile math, one tile over the whole
      trie) — the default serving path and the form CPU CI benchmarks;
    - "dense"  -> the pure-jnp reference (`ref.fleet_plan`): one full
      min-pass per lexicographic key with the (N, Dmax) delay intermediate
      materialized — the oracle tests compare against and the pre-fusion
      baseline `benchmarks/table3_overhead.py` measures.

    All three pick the identical node (exact float32 key comparisons, same
    tie-breaking as the host ``select_path``); inference-only, no vjp.

    ``blocked_depth`` (N,) float32 is the engine-availability mask as a
    node column (fault-tolerant serving): a candidate ``v`` is admissible
    from prefix ``u`` only when ``blocked_depth[v] <= depth[u]``.  ``None``
    (or all-zeros) means every engine is up — identical plans to the
    pre-fault contract.

    Every variant runs under the named scope `PLAN_SCOPE`, so the sweep's
    operations carry it in their HLO metadata wherever the planner is
    traced (the compiled event engine's width-1 sweeps included).
    """
    with jax.named_scope(PLAN_SCOPE):
        if blocked_depth is None:
            blocked_depth = jnp.zeros_like(terminal)
        if use_pallas:
            variant = "pallas"
        if variant == "pallas":
            return _pallas_trie_plan(
                terminal, depth, acc, cost, lat, subtree_size, path_models,
                path_counts, engine_of_model, prefixes, elapsed_lat,
                elapsed_cost, engine_delays, acc_floor, cost_cap, lat_cap,
                kind=kind, blocked_depth=blocked_depth, interpret=_interpret())
        if variant == "fused":
            return fleet_plan_blocked(
                terminal, depth, acc, cost, lat, subtree_size, path_models,
                path_counts, engine_of_model, prefixes, elapsed_lat,
                elapsed_cost, engine_delays, acc_floor, cost_cap, lat_cap,
                kind=kind, blocked_depth=blocked_depth)
        if variant != "dense":
            raise ValueError(
                f"unknown trie_plan variant {variant!r}: {TRIE_PLAN_VARIANTS}")
        return ref.fleet_plan(
            terminal, depth, acc, cost, lat, subtree_size, path_models,
            engine_of_model, prefixes, elapsed_lat, elapsed_cost,
            engine_delays, acc_floor, cost_cap, lat_cap, kind=kind,
            blocked_depth=blocked_depth)
