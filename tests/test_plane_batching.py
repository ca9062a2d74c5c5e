"""Plane-batching bit-identity matrix (ISSUE 9).

The stacked dispatch (``plane_batching="stacked"``) replaces the per-plane
Python loop with one batched program — vmapped charge grid, batched rfft2
convolve, vmapped noise — deriving the SAME per-plane ``fold_in(k, index)``
subkeys, so ADCs must stay bitwise equal to the loop on every executor.
This module pins that contract:

  * 3-plane ADC SHA-256 goldens per charge-grid strategy (stacked path);
    the loopable strategies must reproduce the same digest in loop mode.
  * stacked == loop bitwise across the single-event, batched-event, and
    streaming executors (the distributed executor is covered by the
    subprocess script below, which also counts collectives).
  * the multi-plane strategies (one launch rasterizes ALL planes) refuse
    per-plane dispatch, and ``resolve_plane_batching`` validates the knob.

Re-pin after an intentional physics/RNG change with
``python -m tests.test_plane_batching``.
"""
import hashlib
import json
import os
import subprocess
import sys

import dataclasses
import jax
import numpy as np
import pytest

from repro.config import get_config
from repro.core.batch import event_keys, make_batched_sim_fn, pack_events
from repro.core.depo import generate_physical_depos, generate_plane_depos
from repro.core.pipeline import make_sim_fn
from repro.core.stages import (MULTIPLANE_CHARGE_GRID,
                               PLANE_VMAP_CHARGE_GRID,
                               resolve_plane_batching)

CFG = get_config("lartpc-uboone", smoke=True)
CFG3 = dataclasses.replace(CFG, num_planes=3)

#: 3-plane smoke ADCs, key 0, CPU, stacked dispatch. The multi-plane
#: strategies draw their own RNG streams (fused kernels: in-kernel counter
#: hash; multiplane_xla: single-hash erfinv counters), so their digests
#: differ from the threefry ``unfused`` chain — each pins its own.
#: Refreshed for JAX 0.9.0: its ``jax_threefry_partitionable`` default
#: moves every threefry draw (the generator's tracks and the noise feed
#: every strategy, so all digests moved together).
GOLDEN_ADC3P_SHA256 = {
    "unfused":
        "23a60efbcadc3673ed9e868a286f842a0feffdb993c4a771b19e777cabda5b65",
    "unfused_bf16":
        "96d3ab6b08adb17c9ebc40a549b73653450c58f6442ced4d59b6f1deee5504d4",
    "fused_pallas":
        "5d85362fd00fd6aa73258ac73051300a1554bbfa2cea62092e8621bc975251af",
    "fused_pallas_multiplane":
        "5d85362fd00fd6aa73258ac73051300a1554bbfa2cea62092e8621bc975251af",
    "fused_pallas_multiplane_compact":
        "5d85362fd00fd6aa73258ac73051300a1554bbfa2cea62092e8621bc975251af",
    "multiplane_xla":
        "618782cdd940d8c424e5c9eb9d42f2d424af57a6ec1f33cb23ce01e0be5b0dbb",
}
#: strategies that support BOTH dispatch modes (everything except the
#: multi-plane-only launches, which refuse the per-plane loop)
LOOPABLE = ("unfused", "unfused_bf16", "fused_pallas")


def _cfg3(strategy: str, mode: str = "stacked"):
    return dataclasses.replace(CFG3, charge_grid_strategy=strategy,
                               plane_batching=mode)


def _adc3(cfg) -> np.ndarray:
    key = jax.random.key(0)
    return np.asarray(make_sim_fn(cfg)(key, generate_physical_depos(key, cfg)).adc)


def _sha(adc: np.ndarray) -> str:
    assert adc.dtype == np.int16, adc.dtype
    return hashlib.sha256(adc.tobytes()).hexdigest()


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


class TestGolden3P:
    @pytest.mark.parametrize("strategy", sorted(GOLDEN_ADC3P_SHA256))
    def test_stacked_adc_matches_pin(self, strategy):
        if not _on_cpu():
            pytest.skip("goldens pinned on CPU")
        assert _sha(_adc3(_cfg3(strategy))) == GOLDEN_ADC3P_SHA256[strategy]

    @pytest.mark.parametrize("strategy", LOOPABLE)
    def test_loop_reproduces_stacked_golden(self, strategy):
        """The loop path must hit the SAME pinned digest: stacked-vs-loop
        bit-identity proven against the goldens, not just against each
        other."""
        if not _on_cpu():
            pytest.skip("goldens pinned on CPU")
        assert _sha(_adc3(_cfg3(strategy, "loop"))) \
            == GOLDEN_ADC3P_SHA256[strategy]


class TestExecutorMatrix:
    """stacked == loop bitwise on every in-process executor (default
    threefry strategy; distributed is the subprocess suite below)."""

    def _pair(self, mode):
        return dataclasses.replace(CFG3, plane_batching=mode)

    def test_single_event_executor(self):
        np.testing.assert_array_equal(_adc3(self._pair("stacked")),
                                      _adc3(self._pair("loop")))

    def test_batched_executor(self):
        key = jax.random.key(11)
        events = [generate_plane_depos(jax.random.fold_in(key, e), CFG3)
                  for e in range(2)]
        batch, keys = pack_events(events), event_keys(key, range(2))
        outs = {m: np.asarray(make_batched_sim_fn(self._pair(m))(keys, batch).adc)
                for m in ("stacked", "loop")}
        np.testing.assert_array_equal(outs["stacked"], outs["loop"])

    def test_streaming_executor(self):
        from repro.launch.sim import stream_simulate

        adcs = {}
        for mode in ("stacked", "loop"):
            got = []
            stream_simulate(self._pair(mode), num_events=3, batch_events=2,
                            on_batch=lambda b, nv, nd, dt, out:
                            got.append(np.asarray(out.adc[:nv])))
            adcs[mode] = np.concatenate(got)
        np.testing.assert_array_equal(adcs["stacked"], adcs["loop"])


class TestDispatchRules:
    @pytest.mark.parametrize("strategy", MULTIPLANE_CHARGE_GRID)
    def test_multiplane_strategy_refuses_loop_mode(self, strategy):
        with pytest.raises(ValueError, match="FULL stacked"):
            _adc3(_cfg3(strategy, "loop"))

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError, match="plane_batching"):
            resolve_plane_batching(
                dataclasses.replace(CFG3, plane_batching="zigzag"))

    def test_auto_resolution(self):
        assert resolve_plane_batching(CFG3) == "stacked"
        assert resolve_plane_batching(CFG) == "loop"
        assert resolve_plane_batching(
            dataclasses.replace(CFG3, plane_batching="loop")) == "loop"

    def test_vmap_and_multiplane_sets_disjoint(self):
        assert not set(MULTIPLANE_CHARGE_GRID) & set(PLANE_VMAP_CHARGE_GRID)


class TestTunerKeys:
    """Plane-count-aware autotuner surface: a single-plane winner must not
    key (or be offered for) multi-plane dispatches."""

    def test_charge_grid_shape_carries_plane_count(self):
        from repro.tune import autotune

        assert autotune.op_shape("charge_grid", CFG)["num_planes"] == 1
        assert autotune.op_shape("charge_grid", CFG3)["num_planes"] == 3

    def test_multiplane_strategies_gated_on_plane_axis(self):
        from repro.tune import autotune, registry

        for num_planes, expect in ((1, False), (3, True)):
            cfg = dataclasses.replace(CFG, num_planes=num_planes)
            ctx = registry.make_context(
                cfg, autotune.op_shape("charge_grid", cfg))
            avail = registry.available_strategies("charge_grid", ctx)
            assert ("multiplane_xla" in avail) is expect

    def test_tuner_times_multiplane_candidates(self):
        """The 3-plane tuning problem offers the stacked candidates next to
        the looped single-plane ones — the mechanism by which the tuner
        "proves" the plane-batched path."""
        from repro.tune import autotune

        thunks = autotune.candidate_thunks("charge_grid", CFG3,
                                           sample_depos=32)
        assert "multiplane_xla" in thunks
        assert "unfused" in thunks
        out = thunks["multiplane_xla"]()
        assert out.shape == (3, CFG3.num_wires, CFG3.num_ticks)


# ---------------------------------------------------------------------------
# Distributed executor: subprocess with 8 forced host devices
# ---------------------------------------------------------------------------

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from repro.config import LArTPCConfig
from repro.core.depo import generate_depos, generate_physical_depos
from repro.core.drift import transport_planes
from repro.core.response import (make_distributed_plane_responses,
                                 make_distributed_response)
from repro.core.distributed import (bin_depos_by_wire, make_distributed_sim,
                                    padded_grid_shape, shard_depos)

results = {}
cfg3 = LArTPCConfig(num_wires=128, num_ticks=512, num_depos=256,
                    response_wires=11, response_ticks=64, num_planes=3)
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
w_pad, _, _ = padded_grid_shape(cfg3, 8)
resp3 = make_distributed_plane_responses(cfg3, w_pad)
key = jax.random.key(0)
pdepos = generate_physical_depos(key, cfg3)
sd = shard_depos(pdepos, mesh)
cfg_loop = dataclasses.replace(cfg3, plane_batching="loop")
cfg_st = dataclasses.replace(cfg3, plane_batching="stacked")

# ---- stacked == loop bitwise (psum_scatter, noise + fluctuation on) ----
sim_loop = make_distributed_sim(mesh, cfg_loop, resp3, add_noise=True)
sim_st = make_distributed_sim(mesh, cfg_st, resp3, add_noise=True)
a_loop = np.asarray(sim_loop(key, sd))
a_st = np.asarray(sim_st(key, sd))
results["stacked_eq_loop"] = bool(np.array_equal(a_loop, a_st))

# ---- recon path: stacked == loop for adc / decon / hits ----
simr_loop = make_distributed_sim(mesh, cfg_loop, resp3, add_noise=True,
                                 recon=True)
simr_st = make_distributed_sim(mesh, cfg_st, resp3, add_noise=True,
                               recon=True)
al, dl, hl = simr_loop(key, sd)
as_, ds, hs = simr_st(key, sd)
results["recon_adc_eq"] = bool(np.array_equal(np.asarray(al), np.asarray(as_)))
results["recon_decon_close"] = bool(np.allclose(np.asarray(dl),
                                                np.asarray(ds), atol=1e-5))
results["recon_hits_eq"] = bool(all(
    np.array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree.leaves(hl), jax.tree.leaves(hs))))

# ---- multi-plane halo: per plane BITWISE equal to the single-plane halo
# path (the strong check: the lifted restriction changes nothing per plane)
cfg3nf = dataclasses.replace(cfg_st, fluctuate=False)
ddepos = transport_planes(pdepos, cfg3nf)
binned = bin_depos_by_wire(ddepos, n_strips=4, w_pad=w_pad)
sdb = shard_depos(binned, mesh)
sim_halo = make_distributed_sim(mesh, cfg3nf, resp3,
                                scatter_reduction="halo", add_noise=False)
a_halo = np.asarray(sim_halo(key, sdb))
cfg1nf = dataclasses.replace(cfg3nf, num_planes=1)
plane_eq = []
for p in range(3):
    dp = jax.tree.map(lambda x: x[p], binned)
    sim1h = make_distributed_sim(mesh, cfg1nf, resp3[p],
                                 scatter_reduction="halo", add_noise=False)
    a1h = np.asarray(sim1h(key, shard_depos(dp, mesh)))
    plane_eq.append(bool(np.array_equal(a_halo[p], a1h)))
results["halo_per_plane_bitwise"] = plane_eq

# ---- multi-plane halo vs psum_scatter: same physics, different depo
# ordering (binned + filler rows), so equality is float-accumulation-loose
sim_ps = make_distributed_sim(mesh, cfg3nf, resp3, add_noise=False)
a_ps = np.asarray(sim_ps(key, sd))
results["halo_vs_psum_frac"] = float((a_halo == a_ps).mean())
results["halo_vs_psum_maxdiff"] = int(
    np.abs(a_halo.astype(int) - a_ps.astype(int)).max())

# ---- collective counts: ONE reduce-scatter + ONE all_to_all chain per
# step whatever the plane count; the loop pays P of each. Counting lives in
# repro.analysis.hlo (shared with the contract auditor) — defining
# instructions only, async -start/-done pairs counted once.
from repro.analysis.hlo import collective_counts

def counts(sim, k, d):
    return collective_counts(sim.lower(k, d).compile().as_text())

cfg1 = dataclasses.replace(cfg3, num_planes=1)
resp1 = make_distributed_response(cfg1, w_pad)
sd1 = shard_depos(generate_depos(key, cfg1), mesh)
sim1 = make_distributed_sim(mesh, cfg1, resp1, add_noise=True)
results["collectives_1p"] = counts(sim1, key, sd1)
results["collectives_3p_stacked"] = counts(sim_st, key, sd)
results["collectives_3p_loop"] = counts(sim_loop, key, sd)

print("RESULTS:" + json.dumps(results))
"""

pytestmark_subprocess = pytest.mark.subprocess


@pytest.fixture(scope="module")
def plane_dist_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULTS:")]
    assert line, proc.stdout
    return json.loads(line[0][len("RESULTS:"):])


@pytest.mark.subprocess
class TestDistributedPlaneBatching:
    def test_stacked_equals_loop_bitwise(self, plane_dist_results):
        assert plane_dist_results["stacked_eq_loop"]

    def test_recon_chain_equal(self, plane_dist_results):
        assert plane_dist_results["recon_adc_eq"]
        assert plane_dist_results["recon_decon_close"]
        assert plane_dist_results["recon_hits_eq"]

    def test_multiplane_halo_bitwise_per_plane(self, plane_dist_results):
        assert plane_dist_results["halo_per_plane_bitwise"] == [True] * 3

    def test_halo_vs_psum_scatter(self, plane_dist_results):
        # binned/filler depo reordering makes the comparison float-order
        # loose (the bitwise guarantee is the per-plane check above)
        assert plane_dist_results["halo_vs_psum_frac"] > 0.999
        assert plane_dist_results["halo_vs_psum_maxdiff"] <= 16

    def test_one_collective_chain_per_step(self, plane_dist_results):
        c1 = plane_dist_results["collectives_1p"]
        c_st = plane_dist_results["collectives_3p_stacked"]
        c_loop = plane_dist_results["collectives_3p_loop"]
        assert c_st == c1, (c_st, c1)  # plane count amortized away
        assert c_loop == {k: 3 * v for k, v in c1.items()}, (c_loop, c1)
        # the chains actually exist (the dicts aren't vacuously zero)
        assert c1["reduce-scatter"] > 0 and c1["all-to-all"] > 0, c1


if __name__ == "__main__":
    for strategy in sorted(GOLDEN_ADC3P_SHA256):
        print(f'    "{strategy}":\n        "{_sha(_adc3(_cfg3(strategy)))}",')
