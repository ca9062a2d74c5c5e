"""jit'd public wrapper for the rasterize kernel: DepoSet -> padded patches."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.config import LArTPCConfig
from repro.core.depo import DepoSet, depo_patch_origin
from repro.kernels.rasterize.kernel import rasterize_pallas


def _pad_depos(depos: DepoSet, block: int):
    n = depos.n
    n_pad = (n + block - 1) // block * block
    if n_pad == n:
        return depos, n
    pad = n_pad - n

    def padf(x, fill=0.0):
        return jnp.pad(x, (0, pad), constant_values=fill)

    return DepoSet(
        wire=padf(depos.wire), tick=padf(depos.tick),
        sigma_w=padf(depos.sigma_w, 1.0), sigma_t=padf(depos.sigma_t, 1.0),
        charge=padf(depos.charge),
    ), n


@functools.partial(jax.jit, static_argnames=("cfg", "depo_block", "fluctuate",
                                             "interpret"))
def rasterize_depos(key: jax.Array, depos: DepoSet, cfg: LArTPCConfig,
                    depo_block: int = 256, fluctuate: bool = True,
                    interpret: bool | None = None):
    """Rasterize (+fluctuate) every depo with the Pallas kernel.

    Returns (patches (N, PW_pad, PT_pad), w0, t0) — N is the original count.
    ``interpret=None`` compiles on TPU and interprets elsewhere
    (``repro.kernels.default_interpret``).
    """
    padded, n = _pad_depos(depos, depo_block)
    w0, t0 = depo_patch_origin(padded, cfg)
    pw_pad = (cfg.patch_wires + 7) // 8 * 8
    pt_pad = cfg.pad_ticks
    if fluctuate:
        k1, k2 = jax.random.split(key)
        shape = (padded.n, pw_pad, pt_pad)
        u1 = jax.random.uniform(k1, shape, jnp.float32)
        u2 = jax.random.uniform(k2, shape, jnp.float32)
    else:
        u1 = u2 = jnp.zeros((padded.n, pw_pad, pt_pad), jnp.float32)
    patches = rasterize_pallas(
        padded.wire, padded.tick, padded.sigma_w, padded.sigma_t,
        padded.charge, w0, t0, u1, u2,
        pw=cfg.patch_wires, pt=cfg.patch_ticks, pw_pad=pw_pad, pt_pad=pt_pad,
        depo_block=depo_block, fluctuate=fluctuate, interpret=interpret)
    return patches[:n], w0[:n], t0[:n]
