"""Shared benchmark fixtures: workloads, profiles, annotation caches."""
from __future__ import annotations

import functools
import json
import os

import numpy as np

from repro.core import presets
from repro.core.estimators import ESTIMATORS, annotate
from repro.core.profiler import exhaustive_cost, profile_cascade
from repro.core.trie import Trie
from repro.core.workload import generate_workload

REPORT_DIR = os.path.join(os.path.dirname(__file__), "..", "reports", "bench")
# JAX's persistent compile cache when JAX_COMPILATION_CACHE_DIR is unset:
# one fixed directory of the checkout (ignored by git), so that every
# process of every entry point finds the programs the last one compiled
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")

# paper workload sizes (NL2SQL: |Q| = 1529); MathQA reduced for the 1-core
# container (5460-path trie x requests tables)
SIZES = {"nl2sql_8": 1529, "nl2sql_2": 1000, "mathqa_4": 400}


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache for an entry point and
    return its directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, is
    left to JAX, which reads it itself; otherwise the cache goes to
    `COMPILE_CACHE_DIR`.  Called from ``__main__`` blocks, never on
    import."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


@functools.lru_cache(maxsize=None)
def workload(name: str, seed: int = 0):
    tpl = presets.PRESETS[name]()
    trie = Trie.build(tpl)
    wl = generate_workload(tpl, SIZES[name], seed=seed)
    return trie, wl


@functools.lru_cache(maxsize=None)
def truth(name: str, seed: int = 0):
    trie, wl = workload(name, seed)
    A, C, reached = wl.node_tables(trie)
    return A.mean(axis=0)


@functools.lru_cache(maxsize=None)
def exact_ann(name: str, seed: int = 0):
    trie, wl = workload(name, seed)
    return wl.exact_annotations(trie)


@functools.lru_cache(maxsize=None)
def profile(name: str, coverage: float, seed: int = 0,
            calibration: float = 0.15):
    trie, wl = workload(name, seed)
    return profile_cascade(wl, trie, coverage, seed=seed,
                           calibration_fraction=calibration)


def save_report(name: str, payload) -> str:
    os.makedirs(REPORT_DIR, exist_ok=True)
    path = os.path.join(REPORT_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    return path
