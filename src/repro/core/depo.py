"""Energy depositions ("depos") — the input to the LArTPC signal simulation.

A depo is a point charge deposit from a Geant4-tracked particle. During drift
to the readout plane it becomes a 2-D Gaussian cloud (transverse ×
longitudinal diffusion, Fig. 2 of the paper). The real experiment feeds
CORSIKA+Geant4 output through LArSoft; here ``generate_physical_depos`` is
the stand-in generator producing the same statistical shape — tracks of
correlated *physical* depos — and ``generate_depos`` is that generator plus
the drift stage (``repro.core.drift``), which owns diffusion, lifetime
attenuation, and recombination.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.config import LArTPCConfig


class DepoSet(NamedTuple):
    """Structure-of-arrays depo container (all float32, shape (N,)).

    wire    : transverse center, in wire-pitch units (fractional)
    tick    : longitudinal (drift-time) center, in tick units (fractional)
    sigma_w : transverse Gaussian width, wire units
    sigma_t : longitudinal Gaussian width, tick units
    charge  : number of ionization electrons (mean, pre-fluctuation)
    """

    wire: jax.Array
    tick: jax.Array
    sigma_w: jax.Array
    sigma_t: jax.Array
    charge: jax.Array

    @property
    def n(self) -> int:
        """Depos per plane — the last axis (leaves may carry leading plane
        and/or event axes: (N,), (P, N), (E, P, N))."""
        return self.wire.shape[-1]


def generate_physical_depos(key: jax.Array, cfg: LArTPCConfig,
                            n: int | None = None):
    """Synthetic cosmic-ray-like *physical* depos: straight tracks through
    the volume, in the anode drift frame (``repro.core.drift``).

    Matches the paper's benchmark input statistically: ~100k depos from
    cosmic tracks, deposited at trigger time (t=0) with drift times spanning
    the readout window. Transport to ``(wire, tick)`` detector coordinates —
    diffusion, lifetime attenuation, recombination — is the drift stage's
    job, not the generator's.
    """
    from repro.config import face_extent_mm
    from repro.core.drift import PhysicalDepoSet

    n = n or cfg.num_depos
    n_tracks = max(1, n // 512)  # ~512 depos per track segment
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    # the face: y in wire pitches (num_wires - 1 for the seed box), z in mm
    y_max, z_extent = face_extent_mm(cfg)
    y_max = y_max / cfg.wire_pitch_mm

    # track entry points and direction (in wire/tick coordinates)
    entry_w = jax.random.uniform(k1, (n_tracks,), minval=0.0, maxval=y_max)
    entry_t = jax.random.uniform(k2, (n_tracks,), minval=0.0, maxval=cfg.num_ticks - 1.0)
    theta = jax.random.uniform(k3, (n_tracks,), minval=-1.2, maxval=1.2)

    per = n // n_tracks + 1
    s = jnp.arange(per, dtype=jnp.float32)[None, :]  # arc-length steps along the track
    wires = entry_w[:, None] + jnp.sin(theta)[:, None] * s * 0.5
    ticks = entry_t[:, None] + jnp.cos(theta)[:, None] * s * 2.0
    wires = wires.reshape(-1)[:n]
    ticks = ticks.reshape(-1)[:n]
    # keep everything inside the active volume (reflect)
    wires = jnp.clip(jnp.abs(wires), 0, y_max)
    ticks = jnp.clip(jnp.abs(ticks), 0, cfg.num_ticks - 1)

    # along-wire position z [mm]: tracks slope through the face. Unused by
    # the identity single-plane readout (its projection never reads z, so
    # these draws don't perturb it) but gives the rotated U/V planes of a
    # multi-plane config real geometry.
    k5a, k5b = jax.random.split(k5)
    entry_z = jax.random.uniform(k5a, (n_tracks,), minval=0.0,
                                 maxval=z_extent)
    dz = jax.random.uniform(k5b, (n_tracks,), minval=-2.0, maxval=2.0)
    zs = (entry_z[:, None] + dz[:, None] * s).reshape(-1)[:n]
    zs = jnp.clip(jnp.abs(zs), 0, z_extent)

    # Landau-ish long-tailed charge per depo (lognormal)
    charge = cfg.electrons_per_depo * jnp.exp(
        0.3 * jax.random.normal(k4, (n,))
    )
    return PhysicalDepoSet(
        x=(ticks * cfg.tick_us).astype(jnp.float32),  # drift time [us]
        y=wires.astype(jnp.float32),                  # wire-pitch units
        z=zs.astype(jnp.float32),
        t=jnp.zeros((n,), jnp.float32),               # deposited at trigger
        q=charge.astype(jnp.float32),
    )


def generate_depos(key: jax.Array, cfg: LArTPCConfig, n: int | None = None) -> DepoSet:
    """Physical depo generation + drift transport, as one detector DepoSet.

    Thin wrapper: ``generate_physical_depos`` samples tracks, the drift
    stage transports them to the readout plane. Bit-for-bit with the seed
    repo's direct detector-frame generator at default physics
    (``tests/test_drift.py`` pins this).
    """
    from repro.core.drift import transport

    return transport(generate_physical_depos(key, cfg, n), cfg)


def generate_plane_depos(key: jax.Array, cfg: LArTPCConfig,
                         n: int | None = None) -> DepoSet:
    """Physical depo generation + multi-plane transport: one DepoSet with
    a leading plane axis ``(num_planes, N)`` — the pre-drifted input of the
    streaming launcher in multi-plane configs."""
    from repro.core.drift import transport_planes

    return transport_planes(generate_physical_depos(key, cfg, n), cfg)


def depo_patch_origin(depos: DepoSet, cfg: LArTPCConfig):
    """Integer (wire, tick) origin of each depo's patch, clipped to the grid."""
    w0 = jnp.round(depos.wire).astype(jnp.int32) - cfg.patch_wires // 2
    t0 = jnp.round(depos.tick).astype(jnp.int32) - cfg.patch_ticks // 2
    w0 = jnp.clip(w0, 0, cfg.num_wires - cfg.patch_wires)
    t0 = jnp.clip(t0, 0, cfg.num_ticks - cfg.patch_ticks)
    return w0, t0
