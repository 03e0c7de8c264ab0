"""Seeded inputs of the benchmark: question tables, arrivals, request ids.

Everything here is the benchmark's own copy of the arithmetic, so that no
change to the program can move the yardstick:

- `question_tables` is the synthetic ground-truth generator of the
  workflow's questions (per-question, per-position, per-model success,
  dollar cost and latency), the arithmetic of the program's
  ``generate_workload``; for a configuration with a ``fleet`` section it
  also gives each stage's decode tokens, and prices and times them by
  the engines' curves (`fleet.py`);
- `poisson_arrivals` and `trace_arrivals` are the program's arrival
  generators, draw for draw: a mix of kind ``trace`` extends a short
  Poisson stub by bootstrap;
- `gamma_arrivals` is a renewal process with a set coefficient of
  variation, for bursty traffic (kind ``gamma``);
- `call_inputs` reads one traffic mix and gives the request ids (drawn
  uniformly from the question table) and arrival times of one call of a
  run, from ``(seed, call index)``.

A traffic mix is a JSON file under ``bench/traffic/``:
``{"requests_per_call": n, "arrivals_per_step": k, "arrivals": {...}}``,
read by `call_inputs` alone.
"""
from __future__ import annotations

import numpy as np

import fleet as fleet_count

ARRIVAL_KINDS = ("trace", "gamma")


def stream_seed(seed: int, *words: int) -> int:
    """A 64-bit seed for one named draw of one run: the run's ``seed``
    (any whole number) mixed with the draw's ``words``."""
    entropy = [int(seed) % 2**64, *(int(w) for w in words)]
    return int(np.random.SeedSequence(entropy).generate_state(
        1, np.uint64)[0])


# ----------------------------------------------------------------------
# question tables
# ----------------------------------------------------------------------
def question_tables(models: list[dict], depth: int, n_questions: int,
                    seed: int, *, fleet: dict | None = None,
                    interaction: float = 0.06, depth_decay: float = 0.92):
    """(S, cost, lat): (n_questions, depth, M) success (uint8), dollar
    cost and seconds of each model at each invocation position.

    success prob  pi(q, d, m) = clip(power_m * decay^d * (1 - z_q) + eps_qm)
    with a latent difficulty z_q ~ Beta(1.8, 2.6) and a small zero-mean
    request-model interaction; log-normal output tokens drive the cost
    (price per 1k tokens) and the latency (base + per token + gamma
    noise).  Draw order and arithmetic are those of the program's
    generator, so one seed gives the same tables in both.

    With a ``fleet`` section, (S, cost, lat, decode): the same z, eps and
    S; whole decode tokens log-normal around the fleet's median, longer
    for harder questions and weaker models; and ``lat`` the stage's
    batch-1 seconds on its model's engine, ``prompt_tokens[d] x
    prefill_tok_s + decode x step(1)``, with no noise."""
    rng = np.random.default_rng(seed)
    D, M = depth, len(models)
    z = rng.beta(1.8, 2.6, size=n_questions)
    power = np.array([m["power"] for m in models])
    price = np.array([m["price"] for m in models])
    eps = interaction * rng.standard_normal((n_questions, M))
    decay = depth_decay ** np.arange(D)
    pi = (power[None, None, :] * decay[None, :, None]
          * (1.0 - z[:, None, None]) + eps[:, None, :])
    pi = np.clip(pi, 0.005, 0.97)
    S = (rng.random((n_questions, D, M)) < pi).astype(np.uint8)
    if fleet is not None:
        return (S, *_fleet_tables(models, D, fleet, z, power, price, rng))
    base_lat = np.array([m["base_latency"] for m in models])
    tok_lat = np.array([m["per_token_latency"] for m in models])
    mu_tok = (np.log(260.0) + 0.35 * z[:, None, None]
              + 0.1 * (1 - power)[None, None, :])
    tokens = rng.lognormal(mean=mu_tok, sigma=0.45, size=(n_questions, D, M))
    cost = price[None, None, :] * tokens / 1000.0
    lat = (base_lat[None, None, :] + tok_lat[None, None, :] * tokens
           + rng.gamma(2.0, 0.05, size=(n_questions, D, M)))
    return S, cost, lat.astype(np.float64)


def config_tables(config: dict):
    """The question tables of a configuration, drawn from its own
    ``questions_seed``."""
    wf = config["workflow"]
    return question_tables(wf["models"], len(wf["stages"]),
                           int(config["questions"]),
                           int(config["questions_seed"]),
                           fleet=config.get("fleet"))


def _fleet_tables(models, D, fleet, z, power, price, rng):
    """(cost, lat, decode) of a fleet configuration's questions."""
    prompt = np.asarray(fleet["prompt_tokens"], dtype=np.float64)
    if prompt.shape != (D,):
        raise ValueError(f"prompt_tokens: one per stage ({D}), got "
                         f"{prompt.shape}")
    curves = fleet_count.engines(fleet)
    pf = np.array([curves[m["engine"]].prefill_tok_s for m in models])
    step1 = np.array([curves[m["engine"]].step(1.0) for m in models])
    dec = fleet["decode_tokens"]
    mu = (np.log(float(dec["median"])) + 0.35 * z[:, None, None]
          + 0.1 * (1 - power)[None, None, :])
    decode = np.maximum(1.0, np.rint(rng.lognormal(
        mean=mu, sigma=float(dec["sigma"]), size=(z.size, D, len(models)))))
    cost = price[None, None, :] * decode / 1000.0
    lat = (prompt[None, :, None] * pf[None, None, :]
           + decode * step1[None, None, :])
    return cost, lat, decode


# ----------------------------------------------------------------------
# arrivals
# ----------------------------------------------------------------------
def poisson_arrivals(n: int, rate: float, seed: int) -> np.ndarray:
    """Homogeneous Poisson arrivals: cumulative iid exponential gaps."""
    if n < 0 or not rate > 0:
        raise ValueError(f"need n >= 0 and rate > 0, got {n}, {rate}")
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rate, size=n))


def trace_arrivals(times, n: int, seed: int) -> np.ndarray:
    """A recorded trace cut or extended to ``n`` arrivals: past its end,
    gaps (the first one from the clock's origin) are bootstrap-resampled
    from the trace's own gaps."""
    t = np.sort(np.asarray(times, dtype=np.float64), kind="stable")
    if n > t.size:
        if t.size == 0:
            raise ValueError("cannot extend an empty trace")
        gaps = np.diff(t, prepend=0.0)
        rng = np.random.default_rng(seed)
        extra = rng.choice(gaps, size=n - t.size, replace=True)
        t = np.concatenate([t, t[-1] + np.cumsum(extra)])
    return t[:n]


def gamma_arrivals(n: int, rate: float, cv: float, seed: int) -> np.ndarray:
    """Renewal arrivals with Gamma gaps of mean ``1/rate`` and coefficient
    of variation ``cv`` (cv 1 is Poisson; above 1 the stream is bursty)."""
    if n < 0 or not rate > 0 or not cv > 0:
        raise ValueError(f"need n >= 0, rate > 0, cv > 0; got {n}, {rate}, "
                         f"{cv}")
    shape = 1.0 / (cv * cv)
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.gamma(shape, 1.0 / (rate * shape), size=n))


def call_inputs(mix: dict, n_questions: int, seed: int, call: int):
    """(question ids, arrival times) of call ``call`` of a run: a fresh
    segment of ``mix["requests_per_call"]`` requests whose draws depend on
    ``(seed, call)`` alone.  Every call's virtual clock starts at 0."""
    n = int(mix["requests_per_call"])
    spec = mix["arrivals"]
    kind = spec["kind"]
    if kind == "trace":
        # a short recorded stub, bootstrap-extended to the call's size
        stub = poisson_arrivals(min(n, int(spec["stub"])),
                                float(spec["rate"]),
                                stream_seed(seed, 2, call))
        arr = trace_arrivals(stub, n, stream_seed(seed, 3, call))
    elif kind == "gamma":
        arr = gamma_arrivals(n, float(spec["rate"]), float(spec["cv"]),
                             stream_seed(seed, 2, call))
    else:
        raise ValueError(f"unknown arrival kind {kind!r} (known: "
                         f"{ARRIVAL_KINDS})")
    rng = np.random.default_rng(stream_seed(seed, 1, call))
    reqs = rng.integers(0, n_questions, size=n)
    return reqs.astype(np.int64), arr
