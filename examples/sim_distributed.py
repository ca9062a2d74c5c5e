"""Multi-device LArTPC simulation: depo-parallel rasterization, reduce-scatter
scatter-add, pencil-decomposed distributed FFT — the distributed executor of
the same SimGraph the single-event and batched paths run.

    PYTHONPATH=src python examples/sim_distributed.py [--devices N] [--smoke]

Device count defaults to 8 forced host devices; ``--devices 2 --smoke`` is
the CI distributed smoke (any even N or N=1 works).
"""
import argparse
import os

ap = argparse.ArgumentParser()
ap.add_argument("--devices", type=int, default=8,
                help="forced host device count (even, or 1)")
ap.add_argument("--smoke", action="store_true",
                help="small grid/depo sizes (CI-friendly)")
ap.add_argument("--planes", type=int, default=1,
                help="readout planes (1 = seed single-plane, 3 = U/V/W)")
ap.add_argument("--recon", action="store_true",
                help="also run the recon stages (pencil-FFT deconvolve + "
                     "per-shard hit finding) and report hit counts")
args = ap.parse_args()

os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={args.devices} "
    + os.environ.get("XLA_FLAGS", ""))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.config import LArTPCConfig  # noqa: E402
from repro.core.depo import (generate_depos,  # noqa: E402
                             generate_physical_depos)
from repro.core.distributed import (make_distributed_sim,  # noqa: E402
                                    padded_grid_shape, shard_depos)
from repro.core.response import (make_distributed_plane_responses,  # noqa: E402
                                 make_distributed_response)
from repro.launch.mesh import make_mesh  # noqa: E402

if args.smoke:
    cfg = LArTPCConfig(num_wires=128, num_ticks=512, num_depos=512,
                       response_wires=11, response_ticks=64)
else:
    cfg = LArTPCConfig(num_wires=256, num_ticks=1024, num_depos=4096,
                       response_wires=11, response_ticks=64)
if args.planes > 1:
    import dataclasses
    cfg = dataclasses.replace(cfg, num_planes=args.planes)

n_dev = len(jax.devices())
shape = (n_dev // 2, 2) if n_dev % 2 == 0 else (n_dev, 1)
mesh = make_mesh(shape, ("data", "model"))
print(f"mesh: {dict(mesh.shape)} over {n_dev} devices")

w_pad, _, _ = padded_grid_shape(cfg, n_dev)
key = jax.random.key(0)
if cfg.num_planes > 1:
    # multi-plane runs take PHYSICAL depos: the in-graph drift stage
    # projects them onto every plane's wire direction
    resp = make_distributed_plane_responses(cfg, w_pad)
    depos = generate_physical_depos(key, cfg)
else:
    resp = make_distributed_response(cfg, w_pad)
    depos = generate_depos(key, cfg)
sharded = shard_depos(depos, mesh)
print(f"depos sharded: {sharded[0].sharding}")

sim = make_distributed_sim(mesh, cfg, resp, recon=args.recon)
if args.recon:
    adc, decon, hits = sim(key, sharded)
    print(f"decon out: {decon.shape} {decon.dtype}, "
          f"sharding {decon.sharding}")
    stored = int(np.asarray(hits.mask).sum())
    found = int(np.asarray(hits.n_hits).sum())
    print(f"hits: {stored} stored / {found} found "
          f"(wires {int(np.asarray(hits.wire)[np.asarray(hits.mask)].min())}"
          f"..{int(np.asarray(hits.wire)[np.asarray(hits.mask)].max())})"
          if stored else "hits: none")
    assert stored > 0, "distributed recon found no hits"
else:
    adc = sim(key, sharded)
print(f"ADC out: {adc.shape} {adc.dtype}, sharding {adc.sharding}")
a = np.asarray(adc)[..., :cfg.num_wires, :]
planes = a.reshape((-1,) + a.shape[-2:])
for p, plane in enumerate(planes):
    hit = (np.abs(plane.astype(int) - int(cfg.adc_baseline)) > 5).sum()
    print(f"plane {p}: signal deviation max "
          f"{np.abs(plane - cfg.adc_baseline).max()} counts; "
          f"{hit} hit pixels")
    assert hit > 0, f"distributed sim produced an empty readout (plane {p})"
print("OK")
