"""Distributed tests: run in a subprocess with 8 forced host devices
(the main pytest process must keep the default single device)."""
import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.subprocess

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.config import LArTPCConfig, ModelConfig, SHAPES, ShapeConfig
from repro.core.depo import generate_depos
from repro.core.response import make_response, make_distributed_response
from repro.core.pipeline import simulate_fig4
from repro.core.distributed import (make_distributed_sim, shard_depos,
                                    padded_grid_shape)

results = {}

# ---- distributed LArTPC sim matches single-device cyclic reference ----
cfg = LArTPCConfig(num_wires=128, num_ticks=512, num_depos=256,
                   response_wires=11, response_ticks=64, fluctuate=False)
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
w_pad, _, _ = padded_grid_shape(cfg, 8)
resp = make_distributed_response(cfg, w_pad)
key = jax.random.key(0)
depos = generate_depos(key, cfg)
sd = shard_depos(depos, mesh)
sim = make_distributed_sim(mesh, cfg, resp, add_noise=False)
adc = np.asarray(sim(key, sd))[:cfg.num_wires]

# single-device cyclic reference: scatter + rfft2 multiply at same shape
from repro.core.rasterize import rasterize
from repro.core.scatter import scatter_xla
patches, w0, t0 = rasterize(depos, cfg)
grid = scatter_xla(patches, w0, t0, cfg)
gpad = jnp.zeros((w_pad, cfg.num_ticks)).at[:cfg.num_wires].set(grid)
sig = jnp.fft.irfft2(jnp.fft.rfft2(gpad) * resp.freq,
                     s=(w_pad, cfg.num_ticks))[:cfg.num_wires]
from repro.core.fft_conv import digitize
ref_adc = np.asarray(digitize(sig.astype(jnp.float32), cfg))
results["sim_exact_frac"] = float((adc == ref_adc).mean())
results["sim_maxdiff"] = int(np.abs(adc.astype(int) - ref_adc.astype(int)).max())

# ---- halo-exchange scatter reduction matches psum_scatter ----
# halo needs depos pre-binned by wire strip (strip axis = first mesh axis)
from repro.core.distributed import bin_depos_by_wire
w_pad8, _, _ = padded_grid_shape(cfg, 8)
binned = bin_depos_by_wire(depos, n_strips=4, w_pad=w_pad8)
sdb = shard_depos(binned, mesh, axes=("data", "model"))
sim_halo = make_distributed_sim(mesh, cfg, resp, axes=("data", "model"),
                                scatter_reduction="halo", add_noise=False)
sim_ps = make_distributed_sim(mesh, cfg, resp, axes=("data", "model"),
                              scatter_reduction="psum_scatter",
                              add_noise=False)
a1 = np.asarray(sim_halo(key, sdb))
a2 = np.asarray(sim_ps(key, sdb))
results["halo_vs_psum_frac"] = float((a1 == a2).mean())
results["halo_maxdiff"] = int(np.abs(a1.astype(int) - a2.astype(int)).max())

# ---- sharded train step runs and matches single-device loss ----
from repro.config import OptimizerConfig
from repro.models.model import Model
from repro.optim.adamw import init_opt_state
from repro.train.train_step import make_train_step
from repro.data.tokens import make_batch, shard_batch
from repro.parallel.sharding import use_mesh, act_rules_for
from repro.launch.specs import build_train

mcfg = ModelConfig(num_layers=2, d_model=32, num_heads=4, num_kv_heads=2,
                   d_ff=64, vocab_size=256, remat="none", dtype="float32")
shape = ShapeConfig("t", "train", seq_len=32, global_batch=8)
model = Model(mcfg)
params = model.init(jax.random.key(0))
opt = init_opt_state(params)
batch_np = make_batch(mcfg, shape, seed=0, step=0)

# single device
step1 = jax.jit(make_train_step(model, OptimizerConfig()))
_, _, m1 = step1(params, opt, shard_batch(batch_np))

# 8-device mesh via the launcher specs
with use_mesh(mesh, act_rules_for(mcfg, mesh)):
    fn, _, shardings, kw = build_train(mcfg, shape, mesh)
    psh, osh, bsh = shardings
    params_d = jax.device_put(params, psh)
    opt_d = jax.device_put(opt, osh)
    batch_d = {k: jax.device_put(v, bsh[k]) for k, v in batch_np.items()}
    step8 = jax.jit(fn, in_shardings=shardings, **kw)
    _, _, m8 = step8(params_d, opt_d, batch_d)
results["loss_1dev"] = float(m1["loss"])
results["loss_8dev"] = float(m8["loss"])

print("RESULTS:" + json.dumps(results))
"""


@pytest.fixture(scope="module")
def dist_results():
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
    assert line, proc.stdout
    return json.loads(line[0][len("RESULTS:"):])


def test_distributed_sim_matches_reference(dist_results):
    assert dist_results["sim_exact_frac"] > 0.999
    assert dist_results["sim_maxdiff"] <= 1


def test_halo_equals_psum_scatter(dist_results):
    assert dist_results["halo_vs_psum_frac"] > 0.999
    assert dist_results["halo_maxdiff"] <= 1  # float-order-only differences


def test_sharded_train_step_matches_single_device(dist_results):
    assert abs(dist_results["loss_1dev"] - dist_results["loss_8dev"]) < 2e-3
