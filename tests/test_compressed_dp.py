"""Cross-pod int8-EF compressed DP: subprocess test with 2 forced devices."""
import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.subprocess


SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.config import ModelConfig, OptimizerConfig, ShapeConfig
from repro.data.tokens import make_batch
from repro.models.model import Model
from repro.optim.adamw import init_opt_state
from repro.train.train_step import make_train_step
from repro.train.compressed_dp import (init_compressed_state,
                                       make_compressed_train_step)

cfg = ModelConfig(num_layers=2, d_model=32, num_heads=2, num_kv_heads=2,
                  d_ff=64, vocab_size=128, remat="none", dtype="float32")
shape = ShapeConfig("t", "train", seq_len=32, global_batch=4)
opt_cfg = OptimizerConfig(lr=1e-3, warmup_steps=0, total_steps=50,
                          schedule="constant")
model = Model(cfg)
params0 = model.init(jax.random.key(0))
from repro.launch.mesh import make_mesh
mesh = make_mesh((2,), ("pod",))

# exact (uncompressed) reference on one device
p_ref = params0
s_ref = init_opt_state(p_ref)
step_ref = jax.jit(make_train_step(model, opt_cfg))

# compressed 2-pod run
p_c = params0
s_c = init_compressed_state(p_c, init_opt_state(p_c))
step_c = jax.jit(make_compressed_train_step(model, opt_cfg, mesh))

losses_ref, losses_c = [], []
for t in range(10):
    batch = make_batch(cfg, shape, seed=0, step=t)
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    p_ref, s_ref, m_ref = step_ref(p_ref, s_ref, batch)
    p_c, s_c, m_c = step_c(p_c, s_c, batch)
    losses_ref.append(float(m_ref["loss"]))
    losses_c.append(float(m_c["loss"]))

# compressed training tracks the exact run closely (int8 EF is unbiased)
drift = max(abs(a - b) for a, b in zip(losses_ref, losses_c))
final_gap = abs(losses_ref[-1] - losses_c[-1])
print("RESULTS:" + json.dumps({
    "drift": drift, "final_gap": final_gap,
    "ref0": losses_ref[0], "refN": losses_ref[-1], "cN": losses_c[-1]}))
"""


def test_compressed_dp_tracks_exact():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    # pin children to CPU: with libtpu installed, an unset platform makes
    # the child block on /tmp/libtpu_lockfile held by the pytest process
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULTS:")]
    res = json.loads(line[0][len("RESULTS:"):])
    # both runs must learn, and compressed must track the exact loss curve
    assert res["refN"] < res["ref0"]
    assert res["drift"] < 0.08, res
    assert res["final_gap"] < 0.05, res
