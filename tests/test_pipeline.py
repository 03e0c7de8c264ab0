"""Pipeline parallelism: GPipe over fake CPU devices equals sequential
execution, forward and backward (subprocess isolates the device count)."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_pipeline_matches_sequential():
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.dist.pipeline import pipeline_forward, split_stages

mesh = jax.make_mesh((4,), ("pipe",),
                     axis_types=(jax.sharding.AxisType.Auto,))
L, d, mb, n_micro, S = 8, 16, 2, 6, 4
key = jax.random.PRNGKey(0)
w = jax.random.normal(key, (L, d, d)) * 0.2
b = jax.random.normal(jax.random.PRNGKey(1), (L, d)) * 0.1
x = jax.random.normal(jax.random.PRNGKey(2), (n_micro, mb, S, d))

def layer(p, x):
    wl, bl = p
    return jnp.tanh(x @ wl + bl)

def stage_body(p_stage, x):
    # p_stage: (L/4, d, d), (L/4, d)
    def f(x, p):
        return layer(p, x), ()
    y, _ = jax.lax.scan(f, x, p_stage)
    return y

# sequential reference
def seq(params, x):
    def f(x, p):
        return layer(p, x), ()
    y, _ = jax.lax.scan(f, x, params)
    return y

stages = split_stages((w, b), 4)
out_pipe = pipeline_forward(stages, x, stage_body, mesh=mesh, axis="pipe")
out_seq = jax.vmap(lambda xi: seq((w, b), xi))(x)
np.testing.assert_allclose(np.asarray(out_pipe), np.asarray(out_seq),
                           atol=1e-5)

# backward through the pipeline (ppermute transposes cleanly)
def loss_pipe(stages):
    return (pipeline_forward(stages, x, stage_body, mesh=mesh,
                             axis="pipe") ** 2).sum()

def loss_seq(params):
    return (jax.vmap(lambda xi: seq(params, xi))(x) ** 2).sum()

g_pipe = jax.grad(loss_pipe)(stages)
g_seq = jax.grad(loss_seq)((w, b))
g_seq_staged = split_stages(g_seq, 4)
for a, b_ in zip(jax.tree.leaves(g_pipe), jax.tree.leaves(g_seq_staged := g_seq_staged)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4)
print("PIPELINE_OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=REPO, timeout=300)
    assert "PIPELINE_OK" in r.stdout, (r.stdout[-500:], r.stderr[-2500:])
