"""Stage-graph tests: golden end-to-end regression + executor equivalence.

The refactor contract (ISSUE 4): ``make_sim_fn``, ``make_batched_sim_fn``,
``make_distributed_sim`` (covered in tests/test_distributed.py) and
``stream_simulate`` all execute the SAME SimGraph, and the graph is
bit-for-bit with the pre-graph code. The pinned SHA-256 digests below were
captured from the seed revision (pre-refactor ``simulate_fig4``) on CPU —
any entry point drifting from them is a real regression.
"""
import dataclasses
import hashlib
import re

import jax
import numpy as np
import pytest

from repro.config import get_config
from repro.core.batch import event_keys, make_batched_sim_fn, pack_events
from repro.core.depo import generate_depos, generate_physical_depos
from repro.core.pipeline import make_sim_fn, simulate, simulate_fig4
from repro.core.response import make_response
from repro.core.stages import (FULL_STAGE_ORDER, STAGE_ORDER,
                                 build_sim_graph)

CFG = get_config("lartpc-uboone", smoke=True)

#: captured at the ISSUE 5 noise-normalization fix (CPU backend, default
#: smoke config, key 0) — the Parseval-correct ``noise_spectrum`` changes
#: the additive noise amplitude, which legitimately refreshed the seed-era
#: digests (every entry point moved together; cross-entry-point equality
#: held throughout). The multi-plane refactor landed ON these pins
#: unchanged: the default single-plane config is bit-identical before and
#: after. Digests are backend-specific (erf/FFT/threefry lowering), so the
#: pinned asserts are CPU-only. A jax upgrade that changes RNG or erf
#: lowering legitimately refreshes these: re-run
#: `python -m tests.test_stages` and paste the new values.
#: Refreshed for JAX 0.9.0, whose ``jax_threefry_partitionable`` default
#: (True) draws different threefry bits for the same key. Every pin moved:
#: the depo generator and the noise draw threefry on every strategy. The
#: old values are what the same code gives with that flag set to False.
GOLDEN_ADC_SHA256 = {
    "unfused": "03c6fc7cfdd6b839cb75778937a1c57657e8eea37347b2139b506da36c3105e2",
    "unfused_bf16": "e7e9321abcc7e54e4d65729c24aa5176bc8e51d76b44ec965c2d8851957ea812",
    "fused_pallas": "ef05a4ba0110dc1c184d8d7fa74e75a569bbf02e80029afd77b2bfd5521d2f33",
    "fused_pallas_compact": "ef05a4ba0110dc1c184d8d7fa74e75a569bbf02e80029afd77b2bfd5521d2f33",
}
GOLDEN_BATCHED_E2_SHA256 = (
    "3c0990794609eced935fce8236816a0d03e5135f38d4d367d705386e88e5be1c")

STRATEGIES = sorted(GOLDEN_ADC_SHA256)


def _sha(arr) -> str:
    a = np.ascontiguousarray(np.asarray(arr))
    assert a.dtype == np.int16, a.dtype
    return hashlib.sha256(a.tobytes()).hexdigest()


def _entry_points(cfg):
    """ADC grids from every single-event entry point that must agree: the
    graph executor (make_sim_fn), the legacy wrappers (simulate /
    simulate_fig4), and a raw SimGraph.run — all jit'd, the production
    form (eager bf16 rounds per-op and so differs from any jitted path)."""
    key = jax.random.key(0)
    depos = generate_depos(key, cfg)
    resp = make_response(cfg)
    graph = build_sim_graph(cfg, resp)
    return {
        "make_sim_fn": make_sim_fn(cfg, resp=resp)(key, depos).adc,
        "simulate": jax.jit(
            lambda k, d: simulate(k, d, cfg, resp=resp))(key, depos).adc,
        "simulate_fig4": jax.jit(
            lambda k, d: simulate_fig4(k, d, resp, cfg))(key, depos).adc,
        "graph_run_jit": jax.jit(graph.run)(key, depos).adc,
    }


class TestGolden:
    @pytest.mark.parametrize("strategy", STRATEGIES)
    def test_all_entry_points_agree(self, strategy):
        """Graph and legacy entry points produce one identical ADC grid."""
        cfg = dataclasses.replace(CFG, charge_grid_strategy=strategy)
        grids = _entry_points(cfg)
        digests = {name: _sha(adc) for name, adc in grids.items()}
        assert len(set(digests.values())) == 1, digests

    def test_eager_matches_jit_default_strategy(self):
        """For the float32 default chain, even the eager graph run is
        bit-identical to the jitted executor."""
        key = jax.random.key(0)
        depos = generate_depos(key, CFG)
        graph = build_sim_graph(CFG, make_response(CFG))
        eager = graph.run(key, depos).adc
        jitted = make_sim_fn(CFG)(key, depos).adc
        assert _sha(eager) == _sha(jitted)

    @pytest.mark.parametrize("strategy", STRATEGIES)
    def test_pinned_seed_digest(self, strategy):
        """Fixed key -> SHA of the int16 ADC grid equals the digest captured
        on the seed revision: the refactor is provably bit-for-bit."""
        if jax.default_backend() != "cpu":
            pytest.skip("pinned digests are CPU-lowering specific")
        cfg = dataclasses.replace(CFG, charge_grid_strategy=strategy)
        key = jax.random.key(0)
        adc = make_sim_fn(cfg)(key, generate_depos(key, cfg)).adc
        assert _sha(adc) == GOLDEN_ADC_SHA256[strategy]

    def test_batched_matches_seed_digest(self):
        if jax.default_backend() != "cpu":
            pytest.skip("pinned digests are CPU-lowering specific")
        key = jax.random.key(0)
        events = [generate_depos(jax.random.fold_in(key, e), CFG)
                  for e in range(2)]
        out = make_batched_sim_fn(CFG)(event_keys(key, range(2)),
                                       pack_events(events))
        assert _sha(out.adc) == GOLDEN_BATCHED_E2_SHA256

    def test_batched_rows_equal_single_event_runs(self):
        """The vmap executor and the single-event executor run the same
        graph: per-event rows are bit-identical."""
        key = jax.random.key(5)
        events = [generate_depos(jax.random.fold_in(key, e), CFG)
                  for e in range(3)]
        batch = pack_events(events)
        keys = event_keys(key, range(3))
        out = make_batched_sim_fn(CFG)(keys, batch)
        sim = make_sim_fn(CFG)
        for e in range(3):
            ref = sim(keys[e], batch.event(e))
            np.testing.assert_array_equal(np.asarray(out.adc[e]),
                                          np.asarray(ref.adc))


class TestGraphMechanics:
    @pytest.mark.parametrize("executor", ["single", "batched"])
    def test_responses_are_program_arguments(self, executor):
        """The executors pass the response spectra to the compiled program
        as arguments: baked in as literals they made the full-config
        programs 0.3-1.3 GB, beyond a size-capped compilation cache."""
        resp = make_response(CFG)
        key = jax.random.key(0)
        depos = generate_depos(key, CFG)
        if executor == "single":
            lowered = make_sim_fn(CFG, resp=resp).lower(key, depos)
        else:
            lowered = make_batched_sim_fn(CFG, resp=resp).lower(
                event_keys(key, range(1)), pack_events([depos]))
        literals = re.findall(r"dense<[^>]*>", lowered.as_text())
        assert max(map(len, literals)) < resp.freq.size

    def test_canonical_stage_order(self):
        graph = build_sim_graph(CFG, make_response(CFG))
        assert graph.stage_names == STAGE_ORDER

    def test_no_noise_drops_the_stage(self):
        graph = build_sim_graph(CFG, make_response(CFG), add_noise=False)
        assert "noise" not in graph.stage_names
        assert graph.stage_names[-1] == "digitize"

    def test_physical_input_drifts_inside_the_graph(self):
        """Feeding physical depos to any executor transports them through
        the drift stage — same ADC as pre-drifting by hand."""
        if jax.default_backend() != "cpu":
            # accelerator backends may FMA-fuse the in-graph drift sigma
            # math, making jit-drift vs eager-drift ulp-different
            pytest.skip("bitwise jit-vs-eager drift is CPU-specific")
        key = jax.random.key(1)
        pdepos = generate_physical_depos(key, CFG)
        sim = make_sim_fn(CFG)
        from_physical = sim(key, pdepos)
        from_detector = sim(key, generate_depos(key, CFG))
        np.testing.assert_array_equal(np.asarray(from_physical.adc),
                                      np.asarray(from_detector.adc))

    def test_stage_override(self):
        """SimGraph.replace swaps one stage without touching the executor
        (the mechanism the distributed pipeline specializes through)."""
        graph = build_sim_graph(CFG, make_response(CFG), add_noise=False)
        marker = {}

        def null_charge_grid(state):
            marker["ran"] = True
            import jax.numpy as jnp
            return state._replace(grid=jnp.zeros(
                (CFG.num_wires, CFG.num_ticks), jnp.float32))

        out = graph.replace(charge_grid=null_charge_grid).run(
            jax.random.key(0), generate_depos(jax.random.key(0), CFG))
        assert marker.get("ran")
        adc = np.asarray(out.adc)
        assert (adc == CFG.adc_baseline).all()  # zero grid -> baseline ADC

    def test_override_unknown_stage_raises(self):
        graph = build_sim_graph(CFG, make_response(CFG))
        with pytest.raises(KeyError, match="deconvolve"):
            graph.replace(deconvolve=lambda s: s)

    def test_graph_is_reusable_and_stateless(self):
        graph = build_sim_graph(CFG, make_response(CFG))
        key = jax.random.key(9)
        depos = generate_depos(key, CFG)
        a = graph.run(key, depos).adc
        b = graph.run(key, depos).adc
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_registry_ops_annotated(self):
        """Stages declare the hot op they dispatch, so tooling can map the
        timing board onto the strategy registry."""
        graph = build_sim_graph(CFG, make_response(CFG))
        ops = {s.name: s.op for s in graph.stages}
        assert ops["drift"] == "drift"
        assert ops["charge_grid"] == "charge_grid"
        assert ops["convolve"] == "fft_convolve"
        assert ops["noise"] is None and ops["digitize"] is None


def _op_names(compiled_text: str):
    """Every ``op_name`` (JAX source path) of a compiled program's HLO."""
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


def _scopes(op_names, names):
    """The stage names that occur as a whole element of some op's path."""
    return {n for n in names
            if any(re.search(r"(?:^|[/(])" + n + r"(?:[/)]|$)", p)
                   for p in op_names)}


def _stage_program(kind):
    """(compiled HLO text, stages that do work in it) at the smoke size."""
    from repro.config import apply_overrides
    from repro.core.depo import generate_plane_depos

    key = jax.random.key(0)
    if kind == "single":
        # physical input: the drift stage transports it inside the program
        pdepos = generate_physical_depos(key, CFG)
        text = make_sim_fn(CFG).lower(key, pdepos).compile().as_text()
        return text, STAGE_ORDER
    cfg = CFG if kind != "stacked3p" else apply_overrides(
        CFG, {"num_planes": 3, "plane_batching": "stacked"})
    gen = generate_plane_depos if cfg.num_planes > 1 else generate_depos
    events = [gen(jax.random.fold_in(key, e), cfg) for e in range(2)]
    recon = kind == "recon"
    fn = make_batched_sim_fn(cfg, recon=recon)
    text = fn.lower(event_keys(key, range(2)),
                    pack_events(events)).compile().as_text()
    # packed batches are detector-frame depos: drift passes them through
    stages = tuple(s for s in (FULL_STAGE_ORDER if recon else STAGE_ORDER)
                   if s != "drift")
    return text, stages


class TestStageScopes:
    """Every executor runs each stage under ``jax.named_scope(stage.name)``,
    so a trace of the fused program attributes device time to stages."""

    @pytest.mark.parametrize("kind",
                             ["single", "batched", "recon", "stacked3p"])
    def test_compiled_program_carries_every_stage_scope(self, kind):
        text, stages = _stage_program(kind)
        names = _op_names(text)
        assert _scopes(names, FULL_STAGE_ORDER) == set(stages)

    def test_op_kinds_keep_their_names_inside_the_scopes(self):
        """The scatter-add sits in charge_grid and the transforms in
        convolve, with the op's own name still in its path."""
        text, _ = _stage_program("batched")
        names = _op_names(text)
        assert any("vmap(charge_grid)/" in p and "/scatter" in p
                   for p in names)
        assert any("vmap(convolve)/" in p and "jit(fft)" in p
                   for p in names)

    def test_scopes_leave_the_pinned_digest(self):
        """The scopes are metadata only: the batched program under them
        still gives the pinned ADC digest."""
        if jax.default_backend() != "cpu":
            pytest.skip("pinned digests are CPU-lowering specific")
        key = jax.random.key(0)
        events = [generate_depos(jax.random.fold_in(key, e), CFG)
                  for e in range(2)]
        compiled = make_batched_sim_fn(CFG).lower(
            event_keys(key, range(2)), pack_events(events)).compile()
        assert _scopes(_op_names(compiled.as_text()), STAGE_ORDER) == (
            set(STAGE_ORDER) - {"drift"})
        out = compiled(event_keys(key, range(2)), pack_events(events))
        assert _sha(out.adc) == GOLDEN_BATCHED_E2_SHA256


if __name__ == "__main__":
    # refresh helper: print current digests to paste into the pins above
    key = jax.random.key(0)
    for strategy in STRATEGIES:
        cfg = dataclasses.replace(CFG, charge_grid_strategy=strategy)
        adc = make_sim_fn(cfg)(key, generate_depos(key, cfg)).adc
        print(f'    "{strategy}": "{_sha(adc)}",')
    events = [generate_depos(jax.random.fold_in(key, e), CFG)
              for e in range(2)]
    out = make_batched_sim_fn(CFG)(event_keys(key, range(2)),
                                   pack_events(events))
    print(f'batched_E2: "{_sha(out.adc)}"')
