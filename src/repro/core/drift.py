"""Drift/transport: physical depos -> detector-frame depos (the papers' stage 1).

The source paper's pipeline starts with a *drift* step the seed repo skipped:
Geant4 energy deposits live in detector space and must be transported to the
readout plane before rasterization — picking up drift-time diffusion widths,
electron-lifetime attenuation, and recombination-scaled charge on the way
(paper Fig. 2; larnd-sim ``drifting``/``quenching`` do the same on GPU).

Frames and units
----------------

``PhysicalDepoSet`` uses the **anode drift frame** — the parameterization
Wire-Cell's Drifter hands to the signal simulation:

  x : drift coordinate, measured as drift TIME to the readout plane [us]
      (metric distance / drift speed; transport physics evolves in time)
  y : transverse position across the wire plane, in wire-pitch units
      (the natural transverse metric of a wire readout)
  z : position along the wires [mm] — carried through, unused by the
      single-plane readout
  t : deposition time relative to the trigger [us]
  q : ionization electrons (mean, pre-recombination)

Metric-space tracks (e.g. larnd-sim HDF5 segments, mm) convert **once at
ingestion** via ``PhysicalDepoSet.from_mm``. Keeping unit conversion on the
ingestion boundary rather than inside the jit graph is what makes the
default generator path bit-for-bit with the seed repo: float32 round trips
through non-power-of-two unit constants (``wire -> mm -> wire``) perturb
~15% of values by 1 ulp, while the anode-frame fields need only exact ops
(identity, power-of-two scaling) to reach ``(wire, tick)``.

``drift_depos`` is the vectorized transport itself, registered as the
``drift`` hot op in the strategy registry so the stage graph dispatches it
like every other stage and the autotuner can time future candidates.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.config import LArTPCConfig
from repro.core.depo import DepoSet
from repro.tune.registry import register_strategy, set_default


class PhysicalDepoSet(NamedTuple):
    """Structure-of-arrays physical depo container (all float32, shape (N,)).

    See the module docstring for the anode-drift-frame conventions.
    """

    x: jax.Array
    y: jax.Array
    z: jax.Array
    t: jax.Array
    q: jax.Array

    @property
    def n(self) -> int:
        """Depo count — the last axis (an event axis may lead it)."""
        return self.x.shape[-1]

    def x_mm(self, cfg: LArTPCConfig) -> jax.Array:
        """Metric drift distance [mm] of each depo."""
        return self.x * cfg.drift_speed_mm_us

    def y_mm(self, cfg: LArTPCConfig) -> jax.Array:
        """Metric transverse position [mm] of each depo."""
        return self.y * cfg.wire_pitch_mm

    @classmethod
    def from_mm(cls, x_mm, y_mm, z_mm, t_us, q,
                cfg: LArTPCConfig) -> "PhysicalDepoSet":
        """Ingest metric-space depos (larnd-sim track convention: positions
        in mm, times in us, charge in electrons).

        The single lossy unit conversion of the pipeline happens here, on
        the ingestion boundary.
        """
        f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
        return cls(
            x=f(x_mm) / cfg.drift_speed_mm_us,
            y=f(y_mm) / cfg.wire_pitch_mm,
            z=f(z_mm),
            t=f(t_us),
            q=f(q),
        )


@register_strategy("drift", "jnp",
                   note="vectorized diffusion/attenuation/recombination")
def drift_depos(pdepos: PhysicalDepoSet, cfg: LArTPCConfig) -> DepoSet:
    """Transport physical depos to the readout plane.

    Per depo: arrival tick from deposition time + drift time; diffusion
    widths growing like sqrt(drift time) (paper Fig. 2), floored by
    ``cfg.sigma_w_floor`` / ``cfg.sigma_t_floor`` and clipped so the
    ``nsigma`` extent fits the rasterization patch; charge scaled by the
    recombination survival fraction and (when ``electron_lifetime_us`` > 0)
    attenuated by ``exp(-t_drift / lifetime)`` — larnd-sim's ``drifting``
    kernel, vectorized.

    At default physics (recombination 1.0, lifetime disabled) the charge
    and position paths are exact: ``generate_depos`` routed through this
    stage is bit-identical to the seed formulas (``tests/test_drift.py``).
    """
    t_drift = pdepos.x  # us — the frame is drift-time parameterized
    tick = (pdepos.t + t_drift) / cfg.tick_us
    wire = pdepos.y

    sigma_t = jnp.sqrt(2.0 * cfg.diffusion_long * t_drift) / (
        cfg.drift_speed_mm_us * cfg.tick_us
    ) * cfg.diffusion_scale + cfg.sigma_t_floor
    sigma_w = jnp.sqrt(2.0 * cfg.diffusion_tran * t_drift) / (
        cfg.wire_pitch_mm) * cfg.diffusion_scale + cfg.sigma_w_floor
    # clip so the nsigma extent fits inside the patch; the 0.3 numeric
    # guard yields to a smaller configured floor so sub-0.3 floors stay
    # effective (at the default floors this is exactly the seed clip)
    sigma_w = jnp.clip(sigma_w, min(0.3, cfg.sigma_w_floor),
                       (cfg.patch_wires / 2 - 1) / cfg.nsigma)
    sigma_t = jnp.clip(sigma_t, min(0.3, cfg.sigma_t_floor),
                       (cfg.patch_ticks / 2 - 1) / cfg.nsigma)

    q = pdepos.q * cfg.recombination
    lifetime = cfg.electron_lifetime_us
    if isinstance(lifetime, jax.Array):
        # traced lifetime (gradient-based calibration, repro.core.fit):
        # the enable/disable branch must be data-dependent. The guarded
        # denominator keeps the lifetime<=0 branch NaN-free under grad.
        atten = jnp.exp(-t_drift / jnp.maximum(lifetime, 1e-6))
        q = q * jnp.where(lifetime > 0.0, atten, 1.0)
    elif lifetime > 0.0:
        q = q * jnp.exp(-t_drift / lifetime)

    return DepoSet(
        wire=wire.astype(jnp.float32),
        tick=tick.astype(jnp.float32),
        sigma_w=sigma_w.astype(jnp.float32),
        sigma_t=sigma_t.astype(jnp.float32),
        charge=q.astype(jnp.float32),
    )


set_default("drift", "jnp")


def transport(pdepos: PhysicalDepoSet, cfg: LArTPCConfig) -> DepoSet:
    """Dispatch physical depos -> detector depos through the registry."""
    from repro.tune import autotune, registry

    strategy = cfg.drift_strategy
    if strategy == "auto":
        strategy = autotune.resolve("drift", cfg).strategy
    return registry.get_strategy("drift", strategy).fn(pdepos, cfg)


# ---------------------------------------------------------------------------
# Multi-plane transport (ISSUE 5 tentpole)
# ---------------------------------------------------------------------------


def project_to_plane(pdepos: PhysicalDepoSet, spec, cfg: LArTPCConfig
                     ) -> PhysicalDepoSet:
    """Project the transverse position onto one plane's pitch direction.

    The anode frame carries the transverse position as ``(y, z)`` — ``y``
    across the reference plane in units of ``cfg.wire_pitch_mm``, ``z``
    along its wires in mm. A plane whose wires are rotated by
    ``spec.angle_deg`` from vertical indexes the perpendicular (pitch)
    direction, so its wire coordinate is

        wire_p = (y_mm * cos(angle) + z_mm * sin(angle)) / pitch_p + off_p
               = y * cw + z * cz + off_p

    with ``cw = cos(angle) * wire_pitch_mm / pitch_p`` and
    ``cz = sin(angle) / pitch_p`` precomputed as Python floats. ``off_p``
    centers the plane on the detector face (``face_extent_mm``: y_mm in
    [0, y extent], z in [0, z extent], the generator's volume): the
    plane's middle wire, (spec.num_wires-1)/2, sits at the projected
    midpoint of the face, the convention a real readout uses for its wire
    numbering. Without it a rotated plane's coordinates run one-sided
    (e.g. -60 deg projects z negative-ward only) and a large fraction of
    the event would fall off the low-wire edge. Where the face is the
    seed's square box of ``num_wires`` wires, centering loses the
    symmetric corner overhangs a ±60 deg plane cannot cover with
    ``num_wires`` wires; the face planes of their own wire counts cover
    (``cfg.plane_wires``) has none. The angle-0 reference-pitch plane of the
    seed box has ``cw == 1.0, cz == 0.0, off == 0.0`` and skips the
    arithmetic entirely — bit-identical to the seed single-plane path (no
    lossy unit round trip; see the module docstring).
    """
    import math

    from repro.config import face_extent_mm

    rad = math.radians(spec.angle_deg)
    cos_, sin_ = math.cos(rad), math.sin(rad)
    cw = cos_ * cfg.wire_pitch_mm / spec.pitch_mm
    cz = sin_ / spec.pitch_mm
    y_max, z_max = face_extent_mm(cfg)
    lo = min(0.0, y_max * cos_) + min(0.0, z_max * sin_)
    hi = max(0.0, y_max * cos_) + max(0.0, z_max * sin_)
    off = (spec.num_wires - 1.0) / 2.0 - (lo + hi) / (2.0 * spec.pitch_mm)
    if abs(off) < 1e-6:
        off = 0.0
    if cw == 1.0 and cz == 0.0 and off == 0.0:
        return pdepos
    y = pdepos.y * jnp.float32(cw)
    if cz != 0.0:
        y = y + pdepos.z * jnp.float32(cz)
    if off != 0.0:
        y = y + jnp.float32(off)
    return pdepos._replace(y=y)


def transport_planes(pdepos: PhysicalDepoSet, cfg: LArTPCConfig,
                     planes=None) -> DepoSet:
    """Transport physical depos onto every readout plane at once.

    Returns a ``DepoSet`` whose leaves carry a leading plane axis
    ``(P, N)``: per plane, the transverse position projects onto the
    plane's pitch direction (``project_to_plane``) and the registered
    drift strategy runs with that plane's pitch (transverse diffusion
    widths divide by the *plane's* wire pitch; arrival ticks, longitudinal
    widths, and charge physics are plane-independent). ``planes`` restricts
    to a subset of plane indices (the per-plane timing boards use this);
    None means all ``cfg.num_planes`` planes.
    """
    import dataclasses

    from repro.config import plane_specs

    specs = plane_specs(cfg)
    if planes is not None:
        specs = tuple(specs[p] for p in planes)
    per_plane = []
    for spec in specs:
        pcfg = dataclasses.replace(cfg, wire_pitch_mm=spec.pitch_mm)
        per_plane.append(transport(project_to_plane(pdepos, spec, cfg), pcfg))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_plane)
