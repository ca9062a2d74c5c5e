"""Pallas kernel: threshold-run hit scanner, 128 wires per grid step.

The deconvolved grid arrives time-major, (T, W): grid step j DMAs the
(T, 128) block of wires [128j, 128j + 128) into VMEM and runs the SAME
``scan_runs`` body the XLA strategy runs (a ``fori_loop`` over ticks,
sequential in time and parallel over wires, the decomposition the
hit-finding paper (arXiv:2107.00812) settles on), with one wire per vector
lane. Each tick reads one (1, 128) row of the block; the candidate slots
are (cap, 128) arrays carried through the loop and stored once at the end.
The threshold and per-wire capacity are Python statics (they come from the
config, which is static under jit anyway).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.hitfind import scan_runs
from repro.kernels import default_interpret

#: wires per grid step: one per vector lane
LANES = 128


def _hitfind_kernel(q_ref, counts_ref, hq_ref, hts_ref, hp_ref, *,
                    threshold: float, cap: int):
    """Grid step j: scan the (T, LANES) block of wires for runs.

    counts_ref: (1, LANES) int32; hq/hts/hp_ref: (cap, LANES) float32.
    """
    t_len = q_ref.shape[0]
    n, hq, hts, hp = scan_runs(
        lambda t: q_ref[pl.ds(t, 1), :].astype(jnp.float32), t_len, LANES,
        jnp.float32(threshold), cap)
    counts_ref[...] = n
    hq_ref[...] = hq
    hts_ref[...] = hts
    hp_ref[...] = hp


def hitfind_pallas(q: jax.Array, *, threshold: float, cap: int,
                   interpret: bool | None = None):
    """Run the scanner over a time-major (T, W) grid, W a multiple of 128.

    Returns (counts (1, W) int32, charge (cap, W), tsum (cap, W),
    peak (cap, W)) — ``scan_runs``'s lane-major layout.
    """
    interpret = default_interpret() if interpret is None else interpret
    t_len, w = q.shape
    assert w % LANES == 0, f"pad the wire axis ({w}) to a multiple of {LANES}"
    kernel = functools.partial(_hitfind_kernel, threshold=threshold, cap=cap)
    cand = jax.ShapeDtypeStruct((cap, w), jnp.float32)
    cand_spec = pl.BlockSpec((cap, LANES), lambda j: (0, j))
    return pl.pallas_call(
        kernel,
        grid=(w // LANES,),
        in_specs=[pl.BlockSpec((t_len, LANES), lambda j: (0, j))],
        out_specs=(pl.BlockSpec((1, LANES), lambda j: (0, j)),
                   cand_spec, cand_spec, cand_spec),
        out_shape=(jax.ShapeDtypeStruct((1, w), jnp.int32), cand, cand, cand),
        interpret=interpret,
    )(q)
