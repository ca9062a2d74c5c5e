"""Unit tests for ``repro.analysis.hlo`` — the shared compiled-program
inspection API (ISSUE 10 layer 1).

Two tiers: synthetic HLO text pins the parsing semantics exactly
(``-start``/``-done`` merging, operand references not counted, tuple-type
dtype census, host-callback vs backend custom-calls), and small real jax
programs pin the jax-facing probes (donation request vs realized alias,
x64 leakage, pure_callback detection, cache-miss counting) against the
live lowering pipeline — if a jax upgrade changes the textual conventions,
these fail before the audit baseline silently drifts.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import hlo

# ---------------------------------------------------------------------------
# Synthetic-text tier
# ---------------------------------------------------------------------------

SYNTHETIC = """\
HloModule jit_fn, input_output_alias={ {0}: (0, {}, may-alias), {1}: (2, {}, must-alias) }

fused_computation {
  p0 = f32[8,16]{1,0} parameter(0)
  ROOT m = f32[8,16]{1,0} multiply(p0, p0)
}

ENTRY main {
  %arg0 = f32[8,16]{1,0} parameter(0)
  %ar = f32[8,16]{1,0} all-reduce(%arg0), replica_groups={}
  %ars = f32[8,16]{1,0} all-reduce-start(%ar), replica_groups={}
  %ard = f32[8,16]{1,0} all-reduce-done(%ars)
  %a2a = f32[8,16]{1,0} all-to-all(%ard), replica_groups={}
  %rs = f32[4,16]{1,0} reduce-scatter(%a2a), replica_groups={}
  %cp-start = f32[4,16]{1,0} collective-permute-start(%rs)
  %cp-done = f32[4,16]{1,0} collective-permute-done(%cp-start)
  %sc = bf16[32,64]{1,0} scatter(%arg0, %arg0, %arg0), to_apply=fused_computation
  %fft = c64[8,9]{1,0} custom-call(%ard), custom_call_target="ducc_fft"
  %cb = (f32[8,16]{1,0}, s32[]) custom-call(%sc), custom_call_target="xla_python_cpu_callback"
  %inf = ((f32[2]{0}), token[]) infeed(%cb)
  %snd = (f32[2]{0}, u32[], token[]) send(%inf), is_host_transfer=true
  %snd2 = (f32[2]{0}, u32[], token[]) send(%snd), channel_id=3
  ROOT %t = (f32[8,16]{1,0}, f64[4]{0}, pred[]) tuple(%ar, %ar, %ar)
}
"""


class TestSyntheticText:
    def test_collective_counts_merges_async_pairs(self):
        counts = hlo.collective_counts(SYNTHETIC)
        # all-reduce: one sync + one -start (the -done is skipped)
        assert counts["all-reduce"] == 2
        assert counts["all-to-all"] == 1
        assert counts["reduce-scatter"] == 1
        assert counts["collective-permute"] == 1
        assert counts["all-gather"] == 0  # zeros kept: the dict is total

    def test_operand_references_not_counted(self):
        # "%ar" appears as an operand of several later instructions; only
        # its defining instruction counts
        one_ref = "  %x = f32[2]{0} add(%all-reduce-ish, %y)\n"
        assert hlo.collective_counts(one_ref)["all-reduce"] == 0

    def test_dtype_census_includes_tuple_elements(self):
        census = hlo.dtype_census(SYNTHETIC)
        assert census["f64"] == 1  # only inside the ROOT tuple type
        assert census["pred"] == 1
        assert census["bf16"] == 1
        assert census["c64"] == 1
        assert "f8e4m3fn" not in census

    def test_scatter_output_dtypes(self):
        assert hlo.scatter_output_dtypes(SYNTHETIC) == {"bf16"}

    def test_host_call_count(self):
        # callback custom-call + infeed + host-transfer send = 3;
        # ducc_fft and the channel-only send are NOT host calls
        assert hlo.host_call_count(SYNTHETIC) == 3

    def test_realized_alias_count(self):
        assert hlo.realized_alias_count(SYNTHETIC) == 2
        assert hlo.realized_alias_count("HloModule plain\n") == 0

    def test_iter_instructions_shapes(self):
        ops = [op for op, _, _ in hlo.iter_instructions(SYNTHETIC)]
        assert "parameter" in ops and "tuple" in ops
        assert "scatter" in ops


# ---------------------------------------------------------------------------
# Live-jax tier
# ---------------------------------------------------------------------------


class TestLiveJax:
    def test_donation_requested_and_realized(self):
        """Same-shape donated input: the request AND the realized alias are
        both visible."""

        def f(x):
            return x * 2.0

        jf = jax.jit(f, donate_argnums=(0,))
        lowered = jf.lower(jnp.ones((16, 16), jnp.float32))
        assert hlo.donated_arg_count(lowered) == 1
        assert hlo.realized_alias_count(lowered.compile().as_text()) == 1

    def test_donation_requested_but_unusable_still_counts(self):
        """Shape-changing program: XLA can't alias, but the jit-boundary
        request is still visible — the property the streaming contract
        pins on CPU."""
        import warnings

        def f(x):
            return jnp.sum(x)

        jf = jax.jit(f, donate_argnums=(0,))
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            lowered = jf.lower(jnp.ones((16, 16), jnp.float32))
            txt = lowered.compile().as_text()
        assert hlo.donated_arg_count(lowered) == 1
        assert hlo.realized_alias_count(txt) == 0

    def test_no_donation_counts_zero(self):
        lowered = jax.jit(lambda x: x * 2.0).lower(jnp.ones(4))
        assert hlo.donated_arg_count(lowered) == 0

    def test_pure_callback_is_a_host_call(self):
        def f(x):
            return jax.pure_callback(
                lambda a: a, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

        txt = jax.jit(f).lower(jnp.ones(8)).compile().as_text()
        assert hlo.host_call_count(txt) >= 1

    def test_fft_custom_call_is_not_a_host_call(self):
        txt = jax.jit(lambda x: jnp.fft.rfft(x)).lower(
            jnp.ones(64)).compile().as_text()
        assert hlo.host_call_count(txt) == 0

    def test_x64_leak_shows_in_census(self):
        def f(x):
            return (x.astype(jnp.float64) * jnp.float64(1.0 + 1e-12)  # repro-lint: disable=f64-literal
                    ).astype(jnp.float32)

        with jax.enable_x64():
            txt = jax.jit(f).lower(
                jnp.ones(8, jnp.float32)).compile().as_text()
        assert "f64" in hlo.dtype_census(txt)
        # without x64 the cast silently no-ops (jax warns about the
        # truncation) — the audit MUST trace f64 injections under
        # enable_x64 or they vanish
        with pytest.warns(UserWarning, match="truncated"):
            txt32 = jax.jit(f).lower(
                jnp.ones(8, jnp.float32)).compile().as_text()
        assert "f64" not in hlo.dtype_census(txt32)

    def test_recompile_misses_stable_program(self):
        jf = jax.jit(lambda x: x + 1.0)
        assert hlo.recompile_misses(
            jf, lambda i: (jnp.full((4,), float(i)),)) == 0

    def test_recompile_misses_detects_shape_churn(self):
        jf = jax.jit(lambda x: x + 1.0)
        assert hlo.recompile_misses(
            jf, lambda i: (jnp.ones((4 + i,)),), calls=3) == 2


class TestCollectiveCountsOnRealPrograms:
    """The migrated PR 9 property, through the shared API: single-device
    programs emit no collectives at all."""

    def test_single_device_sim_is_collective_free(self):
        from repro.config import get_config
        from repro.core.depo import generate_physical_depos
        from repro.core.pipeline import make_sim_fn

        cfg = get_config("lartpc-uboone", smoke=True)
        key = jax.random.key(0)
        txt = make_sim_fn(cfg).lower(
            key, generate_physical_depos(key, cfg)).compile().as_text()
        assert hlo.collective_counts(txt) == {
            k: 0 for k in hlo.COLLECTIVE_KINDS}
