"""The executors agree bit for bit per deployment, where no other test holds
them to it: the single-event (``make_sim_fn``) and streaming
(``stream_simulate``) executors on ``lartpc-uboone3p`` without recon, and
the fit's target run (``make_fit_targets``, which drifts inside the graph)
on both deployments, recon off and on. Each is compared with the batched
executor (``make_batched_sim_fn``) on the same events and keys.
"""
import functools

import jax
import numpy as np
import pytest

from repro.config import get_config
from repro.core.batch import event_keys, make_batched_sim_fn, pack_events
from repro.core.depo import generate_depos, generate_plane_depos
from repro.core.fit import make_fit_targets
from repro.core.pipeline import make_sim_fn
from repro.launch.sim import stream_simulate

DEPLOYMENTS = ("lartpc-uboone", "lartpc-uboone3p")
SEED = 13


def _generator(cfg):
    """The streaming launcher's event generator for ``cfg``."""
    return generate_plane_depos if cfg.num_planes > 1 else generate_depos


def _assert_equal(got, want, fields):
    for f in fields:
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(want, f)), err_msg=f)


@functools.lru_cache(maxsize=None)
def _batched(deployment, recon):
    """Two events as ``stream_simulate(seed=SEED)`` draws them, their keys,
    and the batched executor's output."""
    cfg = get_config(deployment, smoke=True)
    key = jax.random.key(SEED)
    batch = pack_events([_generator(cfg)(jax.random.fold_in(key, e), cfg)
                         for e in range(2)])
    keys = event_keys(key, range(2))
    return cfg, keys, batch, make_batched_sim_fn(cfg, recon=recon)(keys,
                                                                  batch)


def test_single_event_runs_equal_batched_rows_three_planes():
    cfg, keys, batch, out = _batched("lartpc-uboone3p", False)
    sim = make_sim_fn(cfg)
    for e in range(2):
        one = sim(keys[e], batch.event(e))
        row = jax.tree.map(lambda x, e=e: x[e], out)
        _assert_equal(one, row, ("adc", "signal"))


def test_streaming_equals_batched_three_planes():
    cfg, _, _, out = _batched("lartpc-uboone3p", False)
    seen = {}

    def on_batch(b, n_valid, n_depos, dt, o):
        seen[b] = jax.tree.map(np.asarray, o)

    stats = stream_simulate(cfg, num_events=2, batch_events=2, seed=SEED,
                            on_batch=on_batch)
    assert stats["events"] == 2
    _assert_equal(seen[0], out, ("adc", "signal"))


@pytest.mark.parametrize("recon", [False, True], ids=["sim", "recon"])
@pytest.mark.parametrize("deployment", DEPLOYMENTS)
def test_fit_targets_equal_the_batched_executor(deployment, recon):
    """The fit's targets come from physical depos drifted inside the graph;
    the batched executor runs the same events drifted by the streaming
    launcher's generator. ADC (and the deconvolved charge) agree bit for
    bit; the fit's target run stops before hit finding."""
    cfg = get_config(deployment, smoke=True)
    key = jax.random.key(SEED)
    targets = make_fit_targets(cfg, key, num_events=2, recon=recon)
    kgen, _ = jax.random.split(key)
    batch = pack_events([_generator(cfg)(jax.random.fold_in(kgen, e), cfg)
                         for e in range(2)])
    out = make_batched_sim_fn(cfg, recon=recon)(targets.keys, batch)
    _assert_equal(targets, out, ("adc", "decon") if recon else ("adc",))
    assert (targets.decon is None) == (not recon)
