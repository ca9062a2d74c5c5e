"""GPipe pipeline-parallel schedule: subprocess test with 4 forced devices."""
import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.subprocess


SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.parallel.pipeline import pipeline_apply

from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("stage",))
n_stages, n_micro, mb, d = 4, 8, 2, 16
key = jax.random.key(0)
w = jax.random.normal(key, (n_stages, d, d)) * 0.3
b = jax.random.normal(jax.random.key(1), (n_stages, d)) * 0.1
params = {"w": w, "b": b}
x = jax.random.normal(jax.random.key(2), (n_micro, mb, d))

def stage_fn(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])

y = pipeline_apply(stage_fn, params, x, mesh, "stage")

# sequential reference
ref = x
for s in range(n_stages):
    ref = jnp.tanh(ref @ w[s] + b[s])
err = float(jnp.max(jnp.abs(y - ref)))
print("RESULTS:" + json.dumps({"err": err}))
"""


def test_gpipe_matches_sequential():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    # pin children to CPU: with libtpu installed, an unset platform makes
    # the child block on /tmp/libtpu_lockfile held by the pytest process
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULTS:")]
    res = json.loads(line[0][len("RESULTS:"):])
    assert res["err"] < 1e-5, res
