"""Sim -> recon round-trip: deconvolution + hit finding close the loop.

The contract under test, end to end: simulate depos to ADC, deconvolve the
ADC back to charge, scan for hits — and get the injected physics back.

 * noiseless runs recover the regularization-attenuated charge grid to a
   few percent (the Wiener inverse is exact up to the attenuation factor
   |R|^2 / (|R|^2 + lambda * max|R|^2) and ADC quantization);
 * noisy runs find hits at the injected depo positions/times;
 * multi-plane configs round-trip bipolar (U/V) and unipolar (W) responses
   through the same stages;
 * every executor (single-event jit, batched vmap, streaming driver; the
   distributed shard_map path lives in its own subprocess test below)
   produces the same hits, bit-for-bit where layouts match and as hit SETS
   where compaction layouts legitimately differ.
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import LArTPCConfig
from repro.core.batch import (event_keys, make_batched_sim_fn, pack_events,
                              simulate_events)
from repro.core.deconvolve import (deconvolve, make_deconv_filter,
                                   measured_signal)
from repro.core.hitfind import HitSet, find_hits, hits_to_tuples
from repro.core.pipeline import make_sim_fn, simulate_fig4
from repro.core.depo import generate_depos, generate_physical_depos
from repro.core.response import make_response
from repro.core.stages import (FULL_STAGE_ORDER, RECON_STAGE_ORDER,
                               build_sim_graph)

CFG = LArTPCConfig(num_wires=64, num_ticks=256, num_depos=48,
                   response_wires=11, response_ticks=48)
NOISELESS = dataclasses.replace(CFG, fluctuate=False)


def _attenuated_reference(grid, resp, lam):
    """What a lambda-regularized Wiener inverse can recover at best: the
    charge grid low-pass filtered by |R|^2 / (|R|^2 + lam * max|R|^2)."""
    w, t = grid.shape
    padded = jnp.zeros(resp.pad_shape, jnp.float32).at[:w, :t].set(grid)
    power = jnp.abs(resp.freq) ** 2
    atten = power / (power + lam * power.max())
    return jnp.fft.irfft2(jnp.fft.rfft2(padded) * atten,
                          s=resp.pad_shape)[:w, :t]


def _interior(arr, cfg):
    """Region away from the crop-boundary wrap of the linear convolution."""
    rw, rt = cfg.response_wires, cfg.response_ticks
    return arr[rw:cfg.num_wires - rw, :cfg.num_ticks - 2 * rt]


class TestNoiselessRoundTrip:
    @pytest.mark.parametrize("plane", ["induction", "collection"])
    def test_recovers_attenuated_charge(self, plane):
        """ADC -> deconvolve returns the attenuated charge grid to a few
        percent, both response polarities (exact-inverse up to the
        regularization attenuation + ADC quantization)."""
        resp = make_response(NOISELESS, plane=plane)
        sim = make_sim_fn(NOISELESS, resp=resp, add_noise=False, recon=True)
        key = jax.random.key(0)
        out = sim(key, generate_depos(key, NOISELESS))
        ref = _attenuated_reference(out.charge_grid, resp,
                                    NOISELESS.deconv_wiener_lambda)
        got = np.asarray(_interior(out.decon, NOISELESS))
        want = np.asarray(_interior(ref, NOISELESS))
        scale = np.abs(want).max()
        assert scale > 100.0  # the event actually hit the interior
        rel = np.abs(got - want).max() / scale
        assert rel < 0.05, f"{plane}: rel={rel:.3e}"

    def test_collection_charge_sum_preserved(self):
        """Unipolar (collection) deconvolution preserves total charge —
        the physics quantity hits integrate downstream."""
        resp = make_response(NOISELESS, plane="collection")
        sim = make_sim_fn(NOISELESS, resp=resp, add_noise=False, recon=True)
        key = jax.random.key(1)
        out = sim(key, generate_depos(key, NOISELESS))
        ratio = float(out.decon.sum()) / float(out.charge_grid.sum())
        assert 0.85 < ratio < 1.25, ratio

    def test_default_graph_has_no_recon_stages(self):
        """recon=False (the default) leaves the forward chain untouched —
        no decon/hits outputs, no extra stages to pay for."""
        g = build_sim_graph(NOISELESS)
        assert tuple(s.name for s in g.stages) == FULL_STAGE_ORDER[:5]
        key = jax.random.key(0)
        out = jax.jit(g.run)(key, generate_physical_depos(key, NOISELESS))
        assert out.decon is None and out.hits is None
        g2 = build_sim_graph(NOISELESS, recon=True)
        assert tuple(s.name for s in g2.stages)[-2:] == RECON_STAGE_ORDER


class TestNoisyHitRecovery:
    def _run(self, seed=0, **over):
        cfg = dataclasses.replace(CFG, **over)
        resp = make_response(cfg, plane="collection")
        sim = make_sim_fn(cfg, resp=resp, recon=True)
        key = jax.random.key(seed)
        depos = generate_depos(jax.random.fold_in(key, 1), cfg)
        return cfg, depos, sim(key, depos)

    def test_hits_land_on_injected_depos(self):
        """With noise + fluctuation on, found hits sit within +/-2 wires and
        +/-5 ticks of an injected depo (collection plane: unipolar, so hit
        positions are directly physical)."""
        cfg, depos, out = self._run()
        hits = out.hits
        n = int(hits.mask.sum())
        assert n > 0
        hw = np.asarray(hits.wire)[np.asarray(hits.mask)]
        ht = np.asarray(hits.tick)[np.asarray(hits.mask)]
        dw = np.asarray(depos.wire)[None, :] - hw[:, None]
        dt = np.asarray(depos.tick)[None, :] - ht[:, None]
        near = (np.abs(dw) <= 2.0) & (np.abs(dt) <= 5.0)
        frac = near.any(axis=1).mean()
        assert frac > 0.8, f"only {frac:.2f} of {n} hits near a depo"

    def test_big_depos_are_found(self):
        """Large-charge depos (well above threshold + noise) each produce
        at least one nearby hit — the recall side of the round trip.

        Recall is pooled over 8 events: a single event's recall swings
        between ~0.6 and 1.0 with the RNG realization (a track running
        along one wire merges into one long run whose mean tick sits more
        than 5 ticks from most of its depos; a track clipped at the window
        edge piles depos onto the last tick), so a one-event bound tested
        the realization, not the recon."""
        found = []
        for seed in range(8):
            cfg, depos, out = self._run(seed=seed)
            hits = out.hits
            hw = np.asarray(hits.wire)[np.asarray(hits.mask)]
            ht = np.asarray(hits.tick)[np.asarray(hits.mask)]
            big = np.asarray(depos.charge) > 3000.0
            assert big.sum() >= 5
            dw = np.abs(np.asarray(depos.wire)[big][:, None]
                        - hw[None, :]) <= 2.0
            dt = np.abs(np.asarray(depos.tick)[big][:, None]
                        - ht[None, :]) <= 5.0
            found.append((dw & dt).any(axis=1))
        recall = np.concatenate(found).mean()
        assert recall > 0.85, f"only {recall:.3f} of big depos recovered"

    def test_truncation_is_detectable_not_silent(self):
        """Starving the HitSet capacity shows up as n_hits > mask.sum()."""
        cfg, depos, out = self._run(max_hits=4, max_hits_per_wire=1)
        hits = out.hits
        assert int(hits.mask.sum()) <= 4
        assert int(hits.n_hits) > int(hits.mask.sum())

    def test_hitset_contract(self):
        """HitSet output contract: fixed capacity, mask-padded, wire-major
        order, int32 wires within range, zeroed padding rows."""
        cfg, depos, out = self._run(seed=3)
        hits = out.hits
        assert isinstance(hits, HitSet)
        assert hits.wire.shape == (cfg.max_hits,)
        assert hits.wire.dtype == jnp.int32 and hits.mask.dtype == jnp.bool_
        m = np.asarray(hits.mask)
        w = np.asarray(hits.wire)
        assert ((w[m] >= 0) & (w[m] < cfg.num_wires)).all()
        order = np.lexsort((np.asarray(hits.tick)[m], w[m]))
        assert (order == np.arange(m.sum())).all()  # stored wire-major
        assert (np.asarray(hits.charge)[~m] == 0.0).all()


class TestMultiPlaneRoundTrip:
    CFG3 = dataclasses.replace(CFG, num_planes=3)

    def test_bipolar_and_unipolar_planes_round_trip(self):
        """U/V (bipolar) and W (unipolar) all deconvolve back to signals
        that track their own charge grids (mean-subtracted correlation),
        and every plane finds hits."""
        cfg = dataclasses.replace(self.CFG3, fluctuate=False)
        sim = make_sim_fn(cfg, add_noise=False, recon=True)
        key = jax.random.key(0)
        out = sim(key, generate_physical_depos(key, cfg))
        assert out.decon.shape == (3, cfg.num_wires, cfg.num_ticks)
        assert out.hits.charge.shape == (3, cfg.max_hits)
        for p in range(3):
            d = np.asarray(out.decon[p]).ravel()
            g = np.asarray(out.charge_grid[p]).ravel()
            d = d - d.mean()
            g = g - g.mean()
            corr = float((d * g).sum() /
                         (np.linalg.norm(d) * np.linalg.norm(g) + 1e-30))
            assert corr > 0.8, f"plane {p}: corr={corr:.3f}"
            assert int(out.hits.mask[p].sum()) > 0, f"plane {p}: no hits"

    def test_collection_plane_keeps_charge(self):
        """Only the W (collection) plane is unipolar: its deconvolved charge
        sum matches its grid; the bipolar planes' sums cancel toward zero."""
        cfg = dataclasses.replace(self.CFG3, fluctuate=False)
        sim = make_sim_fn(cfg, add_noise=False, recon=True)
        key = jax.random.key(1)
        out = sim(key, generate_physical_depos(key, cfg))
        gsum = np.asarray(out.charge_grid.sum(axis=(1, 2)))
        dsum = np.asarray(out.decon.sum(axis=(1, 2)))
        ratio_w = dsum[2] / gsum[2]
        assert 0.85 < ratio_w < 1.25, ratio_w
        for p in (0, 1):
            # induction: the bipolar response suppresses the DC line, so the
            # recovered net charge is well below the unipolar plane's (the
            # discretized kernel leaves a small DC residual — not exactly 0)
            ratio_p = abs(dsum[p]) / abs(gsum[p])
            assert ratio_p < 0.5 * ratio_w, (p, ratio_p, ratio_w)


class TestExecutorEquivalence:
    @pytest.mark.parametrize("strategy", ["scan", "pallas"])
    def test_batched_bit_equal_single(self, strategy):
        """vmap'd recon == per-event recon, bit for bit, per hit_find
        strategy (noise + fluctuation on)."""
        cfg = dataclasses.replace(CFG, hitfind_strategy=strategy)
        resp = make_response(cfg)
        events = [generate_depos(jax.random.fold_in(jax.random.key(0), i),
                                 cfg, n) for i, n in enumerate([9, 17])]
        batch = pack_events(events)
        keys = event_keys(jax.random.key(0), range(2))
        out = simulate_events(keys, batch, resp, cfg, recon=True)
        for e in range(2):
            ref = simulate_fig4(keys[e], batch.event(e), resp, cfg,
                                recon=True)
            np.testing.assert_array_equal(np.asarray(out.adc[e]),
                                          np.asarray(ref.adc))
            np.testing.assert_array_equal(np.asarray(out.decon[e]),
                                          np.asarray(ref.decon))
            for f in HitSet._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(out.hits, f)[e]),
                    np.asarray(getattr(ref.hits, f)), err_msg=f)

    def test_scan_and_pallas_find_identical_hits(self):
        """The two hit_find strategies share the scan body: bit-identical
        HitSets on a real deconvolved event."""
        resp = make_response(CFG, plane="collection")
        sim = make_sim_fn(CFG, resp=resp, recon=True)
        key = jax.random.key(4)
        out = sim(key, generate_depos(key, CFG))
        h1 = find_hits(out.decon, CFG, "scan")
        h2 = find_hits(out.decon, CFG, "pallas")
        for f in HitSet._fields:
            np.testing.assert_array_equal(np.asarray(getattr(h1, f)),
                                          np.asarray(getattr(h2, f)),
                                          err_msg=f)
        assert int(h1.mask.sum()) > 0

    def test_streaming_matches_direct_batch(self):
        """The double-buffered streaming driver with recon=True hands back
        the same hits as a direct batched call on the same event ids."""
        from repro.launch.sim import stream_simulate

        got = {}
        stats = stream_simulate(
            CFG, num_events=2, batch_events=2, seed=0, recon=True,
            on_batch=lambda b, nv, nd, dt, out: got.update({b: out}))
        assert stats["events"] == 2
        key = jax.random.key(0)
        events = [generate_depos(jax.random.fold_in(key, ev), CFG)
                  for ev in range(2)]
        batch = pack_events(events, pad_to=CFG.num_depos)
        ref = simulate_events(event_keys(key, range(2)), batch,
                              make_response(CFG), CFG, recon=True)
        for f in HitSet._fields:
            np.testing.assert_array_equal(np.asarray(getattr(got[0].hits, f)),
                                          np.asarray(getattr(ref.hits, f)),
                                          err_msg=f)

    def test_unknown_strategies_fail_loudly(self):
        resp = make_response(CFG)
        filt = make_deconv_filter(resp, CFG)
        meas = measured_signal(jnp.full((CFG.num_wires, CFG.num_ticks),
                                        CFG.adc_baseline, jnp.int16), CFG)
        with pytest.raises(ValueError, match="deconvolve strategy"):
            deconvolve(meas, filt, "nope")
        with pytest.raises(ValueError, match="hit_find strategy"):
            find_hits(meas, CFG, "nope")


DIST_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.config import LArTPCConfig
from repro.core.deconvolve import deconvolve, make_deconv_filter, measured_signal
from repro.core.depo import generate_depos
from repro.core.distributed import (make_distributed_sim, padded_grid_shape,
                                    shard_depos)
from repro.core.hitfind import find_hits, hits_to_tuples
from repro.core.response import make_distributed_response

cfg = LArTPCConfig(num_wires=128, num_ticks=512, num_depos=256,
                   response_wires=11, response_ticks=64, fluctuate=False)
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
w_pad, _, _ = padded_grid_shape(cfg, 8)
resp = make_distributed_response(cfg, w_pad)
key = jax.random.key(0)
depos = generate_depos(jax.random.fold_in(key, 1), cfg)
sim = make_distributed_sim(mesh, cfg, resp, add_noise=False, recon=True)
adc, decon, hits = sim(key, shard_depos(depos, mesh))

# single-device reference at the SAME cyclic (w_pad, T) shape
ref_decon = deconvolve(measured_signal(adc, cfg), make_deconv_filter(resp, cfg))
masked = jnp.where((jnp.arange(w_pad) < cfg.num_wires)[:, None], ref_decon, 0.0)
ref_hits = find_hits(masked, cfg)

r3 = lambda ts: sorted((w, round(t, 3), round(q, 1)) for w, t, q in ts)
results = {
    "decon_close": bool(np.allclose(np.asarray(decon), np.asarray(ref_decon),
                                    atol=1e-3)),
    "hits_equal": r3(hits_to_tuples(hits)) == r3(hits_to_tuples(ref_hits)),
    "n_stored": int(np.asarray(hits.mask).sum()),
    "n_hits_match": int(hits.n_hits) == int(ref_hits.n_hits),
}
print("RESULTS:" + json.dumps(results))
"""


@pytest.mark.subprocess
def test_distributed_round_trip_matches_single_device():
    """shard_map recon (8 forced host devices, pencil-FFT deconvolve +
    per-shard hit finding) reproduces the single-device hit set exactly."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", DIST_SCRIPT],
                          capture_output=True, text=True, env=env,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULTS:")][0]
    results = json.loads(line[len("RESULTS:"):])
    assert results["decon_close"], results
    assert results["hits_equal"], results
    assert results["n_hits_match"], results
    assert results["n_stored"] > 0, results
