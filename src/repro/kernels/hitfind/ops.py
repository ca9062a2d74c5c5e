"""jit'd wrapper for the Pallas hit scanner."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.hitfind import finish_scan
from repro.kernels.hitfind.kernel import LANES, hitfind_pallas


def find_wire_hits_pallas(decon: jax.Array, *, threshold: float, cap: int,
                          interpret: bool | None = None):
    """(W, T) deconvolved grid -> per-wire candidates, kernel-scanned.

    The grid goes time-major with the wire axis zero-padded to whole
    128-lane blocks (padding wires are scanned and dropped). Returns
    (counts (W,) int32, charge/tick/peak (W, cap) float32) — the same
    layout (and, by the shared scan body, the same bits) as the XLA
    ``scan`` strategy. ``interpret=None`` compiles on TPU and interprets
    elsewhere (``repro.kernels.default_interpret``).
    """
    w = decon.shape[0]
    pad = -w % LANES
    q = jnp.pad(decon.astype(jnp.float32), ((0, pad), (0, 0))).T
    n, hq, hts, hp = hitfind_pallas(q, threshold=threshold, cap=cap,
                                    interpret=interpret)
    return finish_scan(n[:, :w], hq[:, :w], hts[:, :w], hp[:, :w])
