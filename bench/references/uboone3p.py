"""Plain reference of MicroBooNE's three-plane readout (``uboone3p-recon``).

The configuration's own copy of the reference, as ``bench/harness.py``
loads it by the name its file gives (``"reference": "uboone3p"``). It
imports nothing of the program: the geometry, the frozen generator and
the key schedule are written here, the per-plane chain is the plain
reference's (``reference.plane_chain``), and every constant comes from
the configuration's ``sizes``.

Geometry. Three planes read one face of (y, z) mm (``face``): y along
the vertical collection wires' pitch, z along them; y is the span of the
vertical plane's wires, z the height at which the angled planes' wires
span the face's projection. Plane p has
``plane_wires[p]`` wires at ``plane_pitches_mm`` (or ``wire_pitch_mm``),
its wires ``plane_angles_deg[p]`` from vertical, and is centred on the
face: the projection of the face's corners onto its pitch direction runs
from its first wire's edge to its last's. The program stores an event's
planes along one wire axis, plane 0 first, (E, sum of plane_wires, T), and
its hits with a plane axis, (E, P, max_hits).

Key schedule, word for word:

    event key    fold_in(key(seed), event_id)
    depos        tracks(event key): split(event key, 5) -> k1 .. k5,
                 k5 split again for the along-wire draws (``tracks``)
    kf, kn       split(event key)                 charge grid, noise
    plane p      fold_in(kf, p), fold_in(kn, p)
    fluctuation  normal(fold_in(kf, p), (depos, patch_wires, patch_ticks),
                 float32), one normal per patch pixel of plane p
    noise        k1, k2 = split(fold_in(kn, p));
                 normal(k1 | k2, (plane_wires[p], ticks // 2 + 1)),
                 the real and imaginary parts of plane p's spectra
"""
from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench import depogen, reference


def tracks(key, n: int, sizes: dict) -> depogen.PhysicalDepos:
    """``n`` depos on straight tracks of ``depogen.DEPOS_PER_TRACK`` over
    the face: the program's three-plane generator, operation for
    operation (y in wire pitches up to y extent / wire_pitch_mm, z in mm
    up to the z extent, drift time over the readout window)."""
    import jax
    import jax.numpy as jnp

    num_ticks = sizes["num_ticks"]
    y_face, z_extent = face(sizes)
    y_max = y_face / sizes["wire_pitch_mm"]
    n_tracks = max(1, n // depogen.DEPOS_PER_TRACK)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    entry_w = jax.random.uniform(k1, (n_tracks,), minval=0.0, maxval=y_max)
    entry_t = jax.random.uniform(k2, (n_tracks,), minval=0.0,
                                 maxval=num_ticks - 1.0)
    theta = jax.random.uniform(k3, (n_tracks,), minval=-1.2, maxval=1.2)

    per = n // n_tracks + 1
    s = jnp.arange(per, dtype=jnp.float32)[None, :]
    wires = entry_w[:, None] + jnp.sin(theta)[:, None] * s * 0.5
    ticks = entry_t[:, None] + jnp.cos(theta)[:, None] * s * 2.0
    wires = wires.reshape(-1)[:n]
    ticks = ticks.reshape(-1)[:n]
    wires = jnp.clip(jnp.abs(wires), 0, y_max)
    ticks = jnp.clip(jnp.abs(ticks), 0, num_ticks - 1)

    k5a, k5b = jax.random.split(k5)
    entry_z = jax.random.uniform(k5a, (n_tracks,), minval=0.0,
                                 maxval=z_extent)
    dz = jax.random.uniform(k5b, (n_tracks,), minval=-2.0, maxval=2.0)
    zs = (entry_z[:, None] + dz[:, None] * s).reshape(-1)[:n]
    zs = jnp.clip(jnp.abs(zs), 0, z_extent)

    charge = sizes["electrons_per_depo"] * jnp.exp(
        0.3 * jax.random.normal(k4, (n,)))
    return depogen.PhysicalDepos(
        x=(ticks * sizes["tick_us"]).astype(jnp.float32),
        y=wires.astype(jnp.float32),
        z=zs.astype(jnp.float32),
        t=jnp.zeros((n,), jnp.float32),
        q=charge.astype(jnp.float32),
    )


GENERATORS = {"tracks": depogen.Generator(tracks, depogen.tracks_refusal)}


def planes(sizes: dict):
    """(kind, angle_deg, pitch_mm, wires) of each readout plane."""
    n = sizes["num_planes"]
    pitches = sizes["plane_pitches_mm"] or [sizes["wire_pitch_mm"]] * n
    return [(sizes["plane_types"][p], sizes["plane_angles_deg"][p],
             pitches[p], sizes["plane_wires"][p]) for p in range(n)]


def face(sizes: dict):
    """(y, z) in mm of the face the wires cover: y the vertical plane's
    wires times its pitch; z the least height at which an angled plane of
    n wires at pitch p spans the face, (n p - y |cos a|) / |sin a|."""
    (y,) = [pitch * wires for _, angle, pitch, wires in planes(sizes)
            if angle == 0.0]
    z = min((pitch * wires - y * abs(math.cos(math.radians(angle))))
            / abs(math.sin(math.radians(angle)))
            for _, angle, pitch, wires in planes(sizes) if angle != 0.0)
    return y, z


def project(y, z, angle_deg: float, pitch_mm: float, wires: int,
            sizes: dict):
    """Wire coordinate, in float32, on a plane of ``wires`` wires lying
    ``angle_deg`` from vertical and centred on the face:

        wire = (y wire_pitch cos a + z sin a) / pitch + off

    with ``off`` putting the middle wire, (wires - 1) / 2, at the midpoint
    of the face's projection. y is in wire pitches, z in mm; a rounded
    wire coordinate picks a depo's patch origin, so the arithmetic is the
    configuration's, in float32."""
    rad = math.radians(angle_deg)
    cos_, sin_ = math.cos(rad), math.sin(rad)
    y_face, z_face = face(sizes)
    lo = min(0.0, y_face * cos_) + min(0.0, z_face * sin_)
    hi = max(0.0, y_face * cos_) + max(0.0, z_face * sin_)
    off = (wires - 1.0) / 2.0 - (lo + hi) / (2.0 * pitch_mm)
    cw = cos_ * sizes["wire_pitch_mm"] / pitch_mm
    cz = sin_ / pitch_mm
    if abs(off) < 1e-6:
        off = 0.0
    if cw == 1.0 and cz == 0.0 and off == 0.0:
        return y
    w = y * np.float32(cw)
    if cz != 0.0:
        w = w + z * np.float32(cz)
    if off != 0.0:
        w = w + np.float32(off)
    return w


def reference_event(seed: int, event_id: int, sizes: dict, n_depos: int,
                    generator: str, recon: bool) -> reference.EventRef:
    """One event's outputs, plane by plane, each plane at its own wire
    count and response kind, its planes on threads of their own.

    The depos come from the frozen generator on the default device, as the
    program's stream draws them there; the normals from ``jax.random`` on
    the host CPU, by the key schedule in the module's docstring."""
    import jax
    import jax.numpy as jnp

    key = depogen.event_key(seed, event_id)
    phys = GENERATORS[generator].draw(key, n_depos, sizes)
    phys = {f: np.asarray(getattr(phys, f)) for f in phys._fields}
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        kf, kn = jax.random.split(depogen.event_key(seed, event_id))
    nf = sizes["num_ticks"] // 2 + 1
    shape = (n_depos, sizes["patch_wires"], sizes["patch_ticks"])

    def plane(p: int) -> dict:
        kind, angle, pitch, wires = planes(sizes)[p]
        with jax.default_device(cpu):
            normals = np.asarray(
                jax.random.normal(jax.random.fold_in(kf, p), shape,
                                  jnp.float32))
            k1, k2 = jax.random.split(jax.random.fold_in(kn, p))
            noise = (np.asarray(jax.random.normal(k1, (wires, nf))),
                     np.asarray(jax.random.normal(k2, (wires, nf))))
        wire = project(phys["y"], phys["z"], angle, pitch, wires, sizes)
        depos = reference.drift(dict(phys, wire=wire), pitch, sizes)
        return reference.plane_chain(depos, kind, wires, normals, noise,
                                     sizes, recon)

    with ThreadPoolExecutor(max_workers=sizes["num_planes"]) as pool:
        outs = list(pool.map(plane, range(sizes["num_planes"])))
    return reference.event_ref(outs, recon)


def event_view(batch_out: dict, i: int, sizes: dict) -> dict:
    """Event ``i`` of the program's batch as per-plane lists: the arrays
    split along the wire axis at the planes' cumulative wire counts, the
    hits along their plane axis."""
    cuts = np.cumsum(sizes["plane_wires"])[:-1]
    view = {k: np.split(v[i], cuts) for k, v in batch_out.items()
            if k != "hits"}
    if batch_out.get("hits") is not None:
        view["hits"] = [{k: v[i, p] for k, v in batch_out["hits"].items()}
                        for p in range(sizes["num_planes"])]
    return view
