"""`correct` comes out false when the timed path is broken underneath.

Each test drives a whole (tiny, CPU) run with one fault planted in the
program's served path, skipping only the look for a chip:

- an epoch step that returns its state unchanged;
- half of each call's requests left out, the summary scaled up to the
  whole call;
- the planner's answer altered where it is produced.
"""

import run

TINY = {"requests_per_call": 120}


def test_step_that_returns_its_state_unchanged(monkeypatch):
    from repro.core import events_compiled

    def build_step(cfg):
        import jax
        return jax.jit(lambda st, cn, t_hi: st)

    monkeypatch.setattr(events_compiled, "_ENGINE_CACHE", {})
    monkeypatch.setattr(events_compiled, "_build_step", build_step)
    r = run.run_cell("mathqa4.replay", 21, 0.2, False, mix_overrides=TINY)
    assert r["correct"] is False and r["failed"] > 0


def test_half_of_the_batch_left_out():
    from repro.core.events import run_events

    def half(trie, ann, obj, reqs, executor, *, arrivals, **kw):
        n = len(reqs) // 2
        summary, stats = run_events(trie, ann, obj, reqs[:n], executor,
                                    arrivals=arrivals[:n], **kw)
        out = dict(summary, n_requests=len(reqs))
        for k in ("events", "replans", "served", "succeeded", "rejected",
                  "shed", "slo_violations"):
            out[k] = 2 * summary[k]
        return out, stats

    r = run.run_cell("mathqa4.replay", 22, 0.2, False, mix_overrides=TINY,
                     run_events=half)
    assert r["failed"] == 0
    assert r["correct"] is False
    assert r["checks"]["count_gap"]["value"] > 0


def test_planner_answer_altered_where_it_is_produced(monkeypatch):
    import jax.numpy as jnp

    from repro.core import events_compiled

    plan = events_compiled.traced_fleet_plan

    def altered(td, *a, **kw):
        tgt, nxt = plan(td, *a, **kw)
        m = td.path_counts.shape[1]
        return tgt, jnp.where(nxt >= 0, (nxt + 1) % m, nxt)

    monkeypatch.setattr(events_compiled, "_ENGINE_CACHE", {})
    monkeypatch.setattr(events_compiled, "traced_fleet_plan", altered)
    r = run.run_cell("mathqa4.replay", 23, 0.2, False, mix_overrides=TINY)
    assert r["failed"] == 0
    assert r["correct"] is False
