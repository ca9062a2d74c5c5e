"""The ``lane_rows`` scatter-add strategy against ``xla``.

``lane_rows`` views the grid as a table of 128-tick rows, places every
patch row into a 256-lane strip at its tick offset and adds the strips as
whole table rows, in chunks of depos. Its edges: patches whose ticks cross
a 128-tick block boundary, patches in the last block (whose second row
lies past the table and is dropped), the last wire, a tick count that is
not a multiple of 128 (padded table, cropped result) and a depo count that
does not divide into the chunks. Where no two patches overlap every pixel
takes one value, so the two strategies must agree bit for bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tune
from repro.config import LArTPCConfig
from repro.core import scatter as scatter_mod
from repro.core.scatter import LANES, scatter_lane_rows, scatter_xla

PW, PT = 20, 20


def cfg_for(num_ticks, num_wires=64):
    return LArTPCConfig(num_wires=num_wires, num_ticks=num_ticks,
                        num_depos=1, patch_wires=PW, patch_ticks=PT)


def patches_at(w0, t0, seed=0):
    """Random positive patches at the given (clipped) origins."""
    w0 = np.asarray(w0, np.int32)
    t0 = np.asarray(t0, np.int32)
    rng = np.random.default_rng(seed)
    vals = rng.uniform(0.5, 1000.0, size=(w0.size, PW, PT)).astype(np.float32)
    return jnp.asarray(vals), jnp.asarray(w0), jnp.asarray(t0)


def disjoint(num_ticks, num_wires=64):
    """Origins of patches that never overlap, on the first, a middle and
    the last wire group: the last tick origin (in the last block), block-
    crossing offsets r in 109..127, and the first pixel."""
    last_t = num_ticks - PT
    ticks = [last_t]
    for t in (0, 109, 2 * LANES - 5, 3 * LANES - 19):
        if t <= last_t and all(abs(t - u) >= PT for u in ticks):
            ticks.append(t)
    wires = (0, 22, num_wires - PW)
    return [w for w in wires for _ in ticks], [t for _ in wires for t in ticks]


#: (id, num_ticks, depos, depos per chunk): ragged tick counts exercise the
#: padded table; a few depos per chunk force several chunks, the last one
#: padded
CASES = [
    ("aligned_one_chunk", 512, "disjoint", None),
    ("ragged_one_chunk", 500, "disjoint", None),
    ("ragged_short", 130, "disjoint", None),
    ("aligned_chunks_remainder", 512, "disjoint", 4),
    ("ragged_chunks_remainder", 9592 // 16, "disjoint", 2),
    ("ragged_overlap_chunks", 500, "overlap", 7),
    ("aligned_overlap", 768, "overlap", None),
]


@pytest.mark.parametrize("num_ticks,kind,per_chunk",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_lane_rows_matches_xla(num_ticks, kind, per_chunk, monkeypatch):
    cfg = cfg_for(num_ticks)
    if kind == "disjoint":
        patches, w0, t0 = patches_at(*disjoint(num_ticks))
    else:  # 45 origins drawn over the whole grid
        rng = np.random.default_rng(1)
        patches, w0, t0 = patches_at(
            rng.integers(0, cfg.num_wires - PW + 1, 45),
            rng.integers(0, num_ticks - PT + 1, 45), seed=2)
    n = patches.shape[0]
    if per_chunk is not None:
        monkeypatch.setattr(scatter_mod, "STRIP_CHUNK_BYTES",
                            per_chunk * PW * 2 * LANES * 4)
        chunks = -(-n // per_chunk)
        assert chunks > 1 and n % -(-n // chunks), "the last chunk is padded"
    t0_np = np.asarray(t0)
    assert np.any(t0_np % LANES > LANES - PT), "a patch crosses a block edge"
    assert np.any(t0_np == num_ticks - PT) or kind == "overlap"
    ref = np.asarray(scatter_xla(patches, w0, t0, cfg))
    got = np.asarray(jax.jit(lambda p, w, t: scatter_lane_rows(p, w, t, cfg))(
        patches, w0, t0))
    assert got.shape == (cfg.num_wires, num_ticks)
    np.testing.assert_allclose(got.sum(), float(patches.sum()), rtol=1e-5)
    if kind == "disjoint":
        assert np.array_equal(got, ref)
    else:
        np.testing.assert_allclose(got, ref, rtol=1e-6, atol=0)


def test_lane_rows_batched_matches_per_event():
    """Under vmap (the streaming executor's batch axis) each event's grid
    is the one it gets alone, bit for bit."""
    cfg = cfg_for(500)
    events = [patches_at(*disjoint(500), seed=s) for s in (3, 4)]
    stacked = [jnp.stack(x) for x in zip(*events)]
    batched = jax.jit(jax.vmap(
        lambda p, w, t: scatter_lane_rows(p, w, t, cfg)))(*stacked)
    for e, (p, w, t) in enumerate(events):
        assert np.array_equal(np.asarray(batched[e]),
                              np.asarray(scatter_xla(p, w, t, cfg)))


def test_lane_rows_gradient_matches_xla():
    """``lane_rows`` is declared differentiable: the gradient of a weighted
    grid sum with respect to the patches equals the window scatter's."""
    assert tune.is_differentiable("scatter_add", "lane_rows")
    cfg = cfg_for(500)
    rng = np.random.default_rng(5)
    patches, w0, t0 = patches_at(rng.integers(0, cfg.num_wires - PW + 1, 12),
                                 rng.integers(0, 500 - PT + 1, 12), seed=6)
    weight = jnp.asarray(rng.normal(size=(cfg.num_wires, 500)), jnp.float32)

    def loss(fn):
        return jax.grad(lambda p: jnp.sum(fn(p, w0, t0, cfg) * weight))(patches)

    np.testing.assert_allclose(np.asarray(loss(scatter_lane_rows)),
                               np.asarray(loss(scatter_xla)), rtol=1e-6)


def test_lane_rows_is_the_tpu_default_and_cpu_keeps_xla(tmp_path):
    from repro.config import get_config

    assert tune.default_strategy("scatter_add", "tpu") == "lane_rows"
    assert tune.default_strategy("scatter_add", "cpu") == "xla"
    full = get_config("lartpc-uboone")
    assert full.scatter_strategy == "auto"
    d = tune.resolve("scatter_add", full,
                     cache=tune.TuneCache(str(tmp_path / "cache.json")))
    assert (d.strategy, d.source) == (tune.default_strategy("scatter_add"),
                                      "default")
