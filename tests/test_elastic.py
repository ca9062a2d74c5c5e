"""Elastic restart: a checkpoint written by a 1-device job restores onto an
8-device mesh with full resharding, and training continues identically."""
import json
import os
import subprocess
import sys
import tempfile

import pytest

pytestmark = pytest.mark.subprocess


SCRIPT_SAVE = r"""
import os, json
import jax
from repro.config import ModelConfig
from repro.models.model import Model
from repro.optim.adamw import init_opt_state
from repro.ckpt.checkpoint import CheckpointManager

cfg = ModelConfig(num_layers=2, d_model=32, num_heads=4, num_kv_heads=2,
                  d_ff=64, vocab_size=256, remat="none", dtype="float32")
model = Model(cfg)
params = model.init(jax.random.key(0))
opt = init_opt_state(params)
mgr = CheckpointManager(os.environ["CKPT_DIR"], async_save=False)
mgr.save(3, {"params": params, "opt": opt}, extra={"step": 3})
print("SAVED")
"""

SCRIPT_RESTORE = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from jax.sharding import NamedSharding
from repro.config import ModelConfig
from repro.models.model import Model
from repro.optim.adamw import init_opt_state, OptState
from repro.ckpt.checkpoint import CheckpointManager
from repro.parallel.sharding import use_mesh, act_rules_for

cfg = ModelConfig(num_layers=2, d_model=32, num_heads=4, num_kv_heads=2,
                  d_ff=64, vocab_size=256, remat="none", dtype="float32")
model = Model(cfg)
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
params_t = model.init(jax.random.key(0))
opt_t = init_opt_state(params_t)
param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), model.specs(mesh))
shardings = {"params": param_sh,
             "opt": OptState(step=None, m=param_sh, v=param_sh, master=None)}
mgr = CheckpointManager(os.environ["CKPT_DIR"], async_save=False)
restored, extra = mgr.restore(3, {"params": params_t, "opt": opt_t},
                              shardings=None)
# reshard onto the mesh (elastic: checkpoint stores full logical arrays)
params = jax.tree.map(lambda x, s: jax.device_put(x, s),
                      restored["params"], param_sh)
# values identical to the original init regardless of mesh
ok = all(np.allclose(np.asarray(a), np.asarray(b))
         for a, b in zip(jax.tree.leaves(params),
                         jax.tree.leaves(params_t)))
sharded = any(len(x.sharding.device_set) > 1
              for x in jax.tree.leaves(params))
print("RESULTS:" + json.dumps({"values_ok": ok, "sharded": sharded,
                               "step": extra["step"]}))
"""


def test_elastic_restore_onto_bigger_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    # pin children to CPU: with libtpu installed, an unset platform makes
    # the child block on /tmp/libtpu_lockfile held by the pytest process
    env["JAX_PLATFORMS"] = "cpu"
    with tempfile.TemporaryDirectory() as d:
        env["CKPT_DIR"] = d
        cwd = os.path.dirname(os.path.dirname(__file__))
        p1 = subprocess.run([sys.executable, "-c", SCRIPT_SAVE], env=env,
                            capture_output=True, text=True, timeout=600,
                            cwd=cwd)
        assert p1.returncode == 0 and "SAVED" in p1.stdout, p1.stderr[-2000:]
        p2 = subprocess.run([sys.executable, "-c", SCRIPT_RESTORE], env=env,
                            capture_output=True, text=True, timeout=600,
                            cwd=cwd)
        assert p2.returncode == 0, p2.stderr[-2000:]
        line = [l for l in p2.stdout.splitlines() if l.startswith("RESULTS:")]
        res = json.loads(line[0][len("RESULTS:"):])
        assert res["values_ok"] and res["sharded"] and res["step"] == 3
