"""Compile the main path for a described TPU v5e, at full width.

Nothing here runs: each test lowers a program for one chip of a described
``v5e:2x2`` topology and compiles it with the TPU compiler, which refuses
what interpret mode cannot see (block shapes, VMEM and memory limits,
primitives Mosaic cannot lower). Every Pallas kernel that ``"auto"`` or
``--tune`` can select on a TPU is compiled here with ``interpret=False``;
the kernels the compiler refuses are excluded on TPU by their availability
predicates (``core/scatter.py``, ``core/pipeline.py``) and get no test.
The streaming program is compiled with the window scatter and with the
TPU's default scatter, and its optimized HLO records which of them leaves
a loop with one trip per patch.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import get_config
from repro.core.batch import EventBatch, make_batched_sim_fn
from repro.kernels.hitfind.ops import find_wire_hits_pallas
from repro.tune import default_strategy

FULL = dataclasses.replace(
    get_config("lartpc-uboone"), charge_grid_strategy="unfused",
    scatter_strategy="xla", fft_strategy="rfft2", drift_strategy="jnp")
#: device memory of one TPU v5e chip
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("num_wires", [FULL.num_wires, FULL.num_wires // 4],
                         ids=["plane", "quarter_plane"])
def test_hitfind_kernel_compiles(one_chip, num_wires):
    """The hit-finder kernel over a full readout window: one plane, and
    the quarter plane each chip scans when 4 chips split the wires."""
    decon = jax.ShapeDtypeStruct((num_wires, FULL.num_ticks), jnp.float32,
                                 sharding=one_chip)
    fn = jax.jit(lambda d: find_wire_hits_pallas(
        d, threshold=FULL.hit_threshold, cap=FULL.max_hits_per_wire,
        interpret=False))
    compiled = fn.lower(decon).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _total_bytes(compiled) < HBM_BYTES


@pytest.fixture(scope="module")
def streaming_program(one_chip):
    """The streaming executor's device program (one plane, batch of 2
    full-size events, donation on) for a scatter strategy, compiled once."""
    compiled = {}

    def compile_for(strategy):
        if strategy not in compiled:
            events, n = 2, FULL.num_depos
            f = jax.ShapeDtypeStruct((events, n), jnp.float32,
                                     sharding=one_chip)
            batch = EventBatch(
                wire=f, tick=f, sigma_w=f, sigma_t=f, charge=f,
                n_depos=jax.ShapeDtypeStruct((events,), jnp.int32,
                                             sharding=one_chip))
            keys = jax.ShapeDtypeStruct((events,), jax.random.key(0).dtype,
                                        sharding=one_chip)
            cfg = dataclasses.replace(FULL, scatter_strategy=strategy)
            sim = make_batched_sim_fn(cfg, donate=True)
            compiled[strategy] = sim.lower(keys, batch).compile()
        return compiled[strategy]

    return compile_for


@pytest.mark.parametrize("strategy", ["xla", "lane_rows"])
def test_streaming_program_fits_one_chip(streaming_program, strategy):
    """The streaming program fits one chip's HBM, with the window scatter
    and with the TPU's default scatter."""
    total = _total_bytes(streaming_program(strategy))
    assert total < HBM_BYTES
    if strategy == "xla":
        # two f32 (N, 24, 128) patch-sized arrays per event: ~5 GB per batch
        assert 4 * 10**9 < total


@pytest.mark.parametrize("strategy,per_patch_loop",
                         [("xla", True), ("lane_rows", False)])
def test_charge_grid_scatter_loop(streaming_program, strategy,
                                  per_patch_loop):
    """The TPU compiler expands the window scatter into a ``while`` loop
    with one trip per patch (named after the scatter-add it replaces); the
    TPU's default scatter leaves no such loop, only its chunk loop."""
    assert default_strategy("scatter_add", "tpu") == "lane_rows"
    loops = []
    for line in streaming_program(strategy).as_text().splitlines():
        if " while(" not in line:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        if name and "charge_grid" in name.group(1):
            loops.append(name.group(1))
    assert any("scatter-add" in op for op in loops) == per_patch_loop, loops
    assert loops, "the charge grid keeps a loop: per patch or per chunk"


def test_direct_convolve_compiles(one_chip):
    """The TPU's default convolve, ``direct``, over a batch of 2 full
    planes: one convolution at f32 precision (``highest`` operands), no
    FFT, within one chip's memory."""
    from repro.core.fft_conv import fft_convolve
    from repro.core.response import make_response

    assert default_strategy("fft_convolve", "tpu") == "direct"
    resp = make_response(FULL)
    grids = jax.ShapeDtypeStruct((2, FULL.num_wires, FULL.num_ticks),
                                 jnp.float32, sharding=one_chip)
    fn = jax.jit(jax.vmap(lambda g: fft_convolve(g, resp, "direct")))
    compiled = fn.lower(grids).compile()
    text = compiled.as_text()
    convs = [line for line in text.splitlines() if " convolution(" in line]
    assert convs and all("operand_precision={highest,highest}" in line
                         for line in convs), convs
    assert " fft(" not in text
    assert _total_bytes(compiled) < HBM_BYTES
