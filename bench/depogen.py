"""Frozen copy of the program's depo generator: the benchmark's traffic.

``stream_simulate`` draws its events from ``repro.core.depo``; the plain
reference draws the same events from this copy. The copy repeats the
program's generator operation for operation (same JAX calls, same order,
float32), so on the same device both produce the same bits. A later change
to the program's generator then fails the comparison instead of quietly
changing the traffic.

Each event is ``fold_in(key(seed), event_id)``: straight tracks of
``DEPOS_PER_TRACK`` depos through the volume, with lognormal charge,
deposited at trigger time, in the anode drift frame (x: drift time in us,
y: transverse position in wire pitches, z: along the wires in mm).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

#: depos per straight track segment
DEPOS_PER_TRACK = 512


class PhysicalDepos(NamedTuple):
    x: jax.Array  # drift time to the anode [us]
    y: jax.Array  # transverse position [wire pitches]
    z: jax.Array  # along the wires [mm]
    t: jax.Array  # deposition time after the trigger [us]
    q: jax.Array  # ionization electrons


def event_key(seed: int, event_id: int) -> jax.Array:
    """The key of one event of the stream started with ``seed``."""
    return jax.random.fold_in(jax.random.key(seed), event_id)


def tracks(key: jax.Array, n: int, sizes: dict) -> PhysicalDepos:
    """``n`` depos on straight tracks; ``sizes`` is a configuration's sizes
    (``num_wires``, ``num_ticks``, ``tick_us``, ``wire_pitch_mm``,
    ``electrons_per_depo``)."""
    num_wires, num_ticks = sizes["num_wires"], sizes["num_ticks"]
    n_tracks = max(1, n // DEPOS_PER_TRACK)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    entry_w = jax.random.uniform(k1, (n_tracks,), minval=0.0,
                                 maxval=num_wires - 1.0)
    entry_t = jax.random.uniform(k2, (n_tracks,), minval=0.0,
                                 maxval=num_ticks - 1.0)
    theta = jax.random.uniform(k3, (n_tracks,), minval=-1.2, maxval=1.2)

    per = n // n_tracks + 1
    s = jnp.arange(per, dtype=jnp.float32)[None, :]
    wires = entry_w[:, None] + jnp.sin(theta)[:, None] * s * 0.5
    ticks = entry_t[:, None] + jnp.cos(theta)[:, None] * s * 2.0
    wires = wires.reshape(-1)[:n]
    ticks = ticks.reshape(-1)[:n]
    wires = jnp.clip(jnp.abs(wires), 0, num_wires - 1)
    ticks = jnp.clip(jnp.abs(ticks), 0, num_ticks - 1)

    k5a, k5b = jax.random.split(k5)
    z_extent = num_wires * sizes["wire_pitch_mm"]
    entry_z = jax.random.uniform(k5a, (n_tracks,), minval=0.0,
                                 maxval=z_extent)
    dz = jax.random.uniform(k5b, (n_tracks,), minval=-2.0, maxval=2.0)
    zs = (entry_z[:, None] + dz[:, None] * s).reshape(-1)[:n]
    zs = jnp.clip(jnp.abs(zs), 0, z_extent)

    charge = sizes["electrons_per_depo"] * jnp.exp(
        0.3 * jax.random.normal(k4, (n,)))
    return PhysicalDepos(
        x=(ticks * sizes["tick_us"]).astype(jnp.float32),
        y=wires.astype(jnp.float32),
        z=zs.astype(jnp.float32),
        t=jnp.zeros((n,), jnp.float32),
        q=charge.astype(jnp.float32),
    )


def tracks_refusal(traffic: dict) -> Optional[str]:
    """Why ``tracks`` cannot draw a traffic file's events, or None."""
    if traffic.get("depos_per_track") != DEPOS_PER_TRACK:
        return ("the program's stream draws tracks of "
                f"{DEPOS_PER_TRACK} depos only")
    return None


class Generator(NamedTuple):
    """A frozen generator, ``draw(key, n, sizes)``, and ``refusal(traffic)``:
    why it cannot stand for the program's stream on that traffic, or None."""

    draw: Callable[[jax.Array, int, dict], PhysicalDepos]
    refusal: Callable[[dict], Optional[str]]


GENERATORS = {"tracks": Generator(tracks, tracks_refusal)}
