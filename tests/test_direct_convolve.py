"""The ``direct`` convolve strategy: the linear convolution with the
detector response as one block-Toeplitz convolution, without a transform.

  * it equals ``rfft2`` on induction and collection responses, at tick
    counts that are and are not multiples of the 128-tick block, with
    responses shorter and longer than one block;
  * the three-plane convolve stage (planes of unequal wire count on one
    wire axis) gives the same signal with it as with ``rfft2``;
  * it is differentiable in the grid and in the response;
  * the registry offers it on the TPU only, and never for a response that
    is not padded to the linear-convolution size (a cyclic response) or
    spans too many tick blocks;
  * the deconvolution's ``fft_reuse`` never resolves to it: the inverse
    filter carries the forward kernel, which ``direct`` would apply.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import LArTPCConfig, get_config
from repro.core.deconvolve import (deconvolve, deconvolve_rfft2,
                                   make_deconv_filter)
from repro.core.fft_conv import (MAX_TICK_BLOCKS, TICK_BLOCK, fft_convolve,
                                 fft_shape, resolve_fft_strategy, tick_blocks)
from repro.core.gradcheck import (gradcheck, stage_gradcheck_cases,
                                  stage_gradcheck_suite)
from repro.core.response import make_distributed_response, make_response
from repro.core.stages import SimState, convolve_stage
from repro.tune import autotune, registry


def _rel_l2(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _cfg(num_ticks, response_ticks, num_wires=48, response_wires=21):
    return LArTPCConfig(num_wires=num_wires, num_ticks=num_ticks,
                        num_depos=64, response_wires=response_wires,
                        response_ticks=response_ticks)


@pytest.mark.parametrize("plane", ["induction", "collection"])
@pytest.mark.parametrize("num_ticks", [1024, 1000])
@pytest.mark.parametrize("response_ticks", [64, 200])
def test_direct_equals_rfft2(plane, num_ticks, response_ticks):
    """Relative L2 within 1e-5 of the transform, whatever the block
    alignment of the grid and the number of blocks the kernel spans."""
    cfg = _cfg(num_ticks, response_ticks)
    resp = make_response(cfg, plane=plane)
    grid = jax.random.uniform(jax.random.key(1),
                              (cfg.num_wires, cfg.num_ticks))
    out = fft_convolve(grid, resp, "direct")
    assert out.shape == grid.shape and out.dtype == jnp.float32
    assert _rel_l2(out, fft_convolve(grid, resp, "rfft2")) <= 1e-5


@pytest.mark.parametrize("response_wires", [1, 4, 11])
def test_direct_centres_the_kernel_on_its_middle_wire(response_wires):
    """Odd and even wire spans, and a single wire, are aligned as the
    transform aligns them (the kernel rolled by ``rw // 2``)."""
    cfg = _cfg(300, 129, num_wires=24, response_wires=response_wires)
    resp = make_response(cfg)
    grid = jnp.zeros((cfg.num_wires, cfg.num_ticks)).at[7, 41].set(1.0)
    assert _rel_l2(fft_convolve(grid, resp, "direct"),
                   fft_convolve(grid, resp, "rfft2")) <= 1e-5


def test_direct_under_vmap_batches_the_events():
    cfg = _cfg(1000, 200)
    resp = make_response(cfg)
    grids = jax.random.uniform(jax.random.key(2),
                               (2, cfg.num_wires, cfg.num_ticks))
    out = jax.vmap(lambda g: fft_convolve(g, resp, "direct"))(grids)
    for e in range(2):
        assert _rel_l2(out[e], fft_convolve(grids[e], resp, "rfft2")) <= 1e-5


def test_three_plane_stage_direct_equals_rfft2():
    """Planes of 96 / 96 / 128 wires on one wire axis: each plane is
    convolved at its own wire count with its own response kind."""
    cfg = get_config("lartpc-uboone3p", smoke=True)
    key = jax.random.key(3)
    grid = jax.random.uniform(key, (sum(cfg.plane_wires), cfg.num_ticks))
    state = SimState(key=key, kf=key, kn=key, depos=None, grid=grid)
    out = {s: convolve_stage(dataclasses.replace(cfg, fft_strategy=s),
                             None).fn(state).signal
           for s in ("direct", "rfft2")}
    assert out["direct"].shape == grid.shape
    assert _rel_l2(out["direct"], out["rfft2"]) <= 1e-5


def test_gradcheck_in_the_grid():
    """The grid's gradient through ``direct`` matches central differences
    along a random direction, and equals the transform's gradient."""
    cfg = _cfg(300, 129, num_wires=24)
    resp = make_response(cfg)
    k0, k1, k2 = jax.random.split(jax.random.key(4), 3)
    shape = (cfg.num_wires, cfg.num_ticks)
    grid0 = jax.random.uniform(k0, shape)
    d = jax.random.normal(k1, shape)
    w = jax.random.normal(k2, shape)

    def objective(strategy):
        return lambda g: jnp.sum(fft_convolve(g, resp, strategy) * w)

    res = gradcheck(lambda th: objective("direct")(grid0 + th[0] * d),
                    jnp.asarray([0.0]), name="direct/grid", eps=1e-2,
                    rtol=1e-2)
    assert res.ok, res
    assert _rel_l2(jax.grad(objective("direct"))(grid0),
                   jax.grad(objective("rfft2"))(grid0)) <= 1e-5


def test_gradcheck_in_the_response_gain_and_shaping():
    """The stage matrix's convolve case (``response_gain`` and
    ``response_shaping_us``) with the convolve on ``direct``."""
    (case,) = [c for c in stage_gradcheck_cases()
               if c.name.startswith("convolve/")]
    cfg = dataclasses.replace(get_config("lartpc-uboone", smoke=True),
                              fft_strategy="direct")
    (res,) = stage_gradcheck_suite(cfg, cases=[case])
    assert res.ok, res


@pytest.mark.parametrize("backend,name", [("cpu", "rfft2"),
                                          ("tpu", "direct")])
def test_backend_defaults(backend, name):
    assert registry.default_strategy("fft_convolve", backend) == name


def test_direct_is_differentiable():
    assert registry.is_differentiable("fft_convolve", "direct")


def test_available_refuses_a_cyclic_response():
    """``make_distributed_response`` pads to the readout size (cyclic):
    the predicate refuses it, and every name resolves to ``rfft2``."""
    cfg = _cfg(512, 64, num_wires=64, response_wires=11)
    cyclic = make_distributed_response(cfg, cfg.num_wires)
    shape = dict(fft_shape((cfg.num_wires, cfg.num_ticks), cyclic),
                 pad_wires=cyclic.pad_shape[0],
                 pad_ticks=cyclic.pad_shape[1])
    direct = registry.get_strategy("fft_convolve", "direct")
    assert not direct.is_available(registry.make_context(None, shape,
                                                         backend="tpu"))
    grid = jax.random.uniform(jax.random.key(5), (cfg.num_wires,
                                                  cfg.num_ticks))
    assert resolve_fft_strategy("direct", grid.shape, cyclic) == "rfft2"
    np.testing.assert_array_equal(
        np.asarray(fft_convolve(grid, cyclic, "direct")),
        np.asarray(fft_convolve(grid, cyclic, "rfft2")))
    linear = make_response(cfg)
    assert resolve_fft_strategy("direct", grid.shape, linear) == "direct"


def test_available_refuses_a_kernel_over_four_tick_blocks():
    longest = (MAX_TICK_BLOCKS - 1) * TICK_BLOCK + 1
    assert tick_blocks(200) == 3 and tick_blocks(longest) == MAX_TICK_BLOCKS
    direct = registry.get_strategy("fft_convolve", "direct")
    for rt, ok in ((longest, True), (longest + 1, False)):
        cfg = _cfg(2048, rt)
        ctx = registry.make_context(cfg, autotune.op_shape("fft_convolve",
                                                           cfg),
                                    backend="tpu")
        assert direct.is_available(ctx) is ok


def _deconv_problem():
    cfg = _cfg(512, 64, num_wires=64, response_wires=11)
    resp = make_response(cfg)
    filt = make_deconv_filter(resp, cfg)
    meas = jax.random.normal(jax.random.key(6), (cfg.num_wires,
                                                 cfg.num_ticks))
    return meas, filt


@pytest.mark.parametrize("source", ["tuning_cache", "default"])
def test_fft_reuse_never_applies_the_forward_kernel(source, tmp_path,
                                                    monkeypatch):
    """A tuning-cache entry or a default naming ``direct`` for the forward
    convolve leaves ``fft_reuse`` on the spectral ``rfft2`` layout."""
    meas, filt = _deconv_problem()
    monkeypatch.setenv(autotune.CACHE_ENV, str(tmp_path / "cache.json"))
    shape = fft_shape(meas.shape, filt)
    if source == "tuning_cache":
        ctx = registry.make_context(None, shape)
        autotune.TuneCache().put(
            autotune.cache_key("fft_convolve", ctx.backend, ctx.device_kind,
                               shape), {"strategy": "direct"})
    else:
        monkeypatch.setitem(registry._DEFAULTS["fft_convolve"],
                            registry.current_backend(), "direct")
    assert autotune.resolve("fft_convolve", None,
                            shape=shape).strategy == "direct"
    out = deconvolve(meas, filt, "fft_reuse")
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(deconvolve_rfft2(meas, filt)))
    assert _rel_l2(fft_convolve(meas, filt, "direct"), out) > 0.5
