"""The check that decides ``correct``, at a size a test run holds.

Each cell's chain runs on the CPU at the smoke size of its registry entry
(128 wires x 512 ticks, 256 depos per event), against the plain reference,
with the cell's own limits. The program passes; its lower-precision path
(bfloat16 patches, the control) fails; and so does each fault the cell can
have, planted in the timed program: an answer altered where it is
produced, and, where a batch holds more than one event, half of the batch
left out (its events given the other half's outputs)."""
import dataclasses

import jax
import pytest

from bench import check, harness

SEED = 3_000_000_017


def smoke_cell(name: str, batch_events=None) -> harness.Cell:
    from repro.config import get_config

    cell = harness.load_cell(name)
    smoke = get_config(cell.config["registry"], smoke=True)
    sizes = dict(cell.sizes)
    for key in sizes:
        if key != "num_planes":
            v = getattr(smoke, key)
            sizes[key] = list(v) if isinstance(v, tuple) else v
    overrides = {k: v for k, v in cell.config.get("overrides", {}).items()
                 if k not in sizes}
    return dataclasses.replace(
        cell, config=dict(cell.config, smoke=True, sizes=sizes,
                          overrides=overrides),
        traffic=dict(cell.traffic, depos_per_event=256),
        batch_events=batch_events or cell.batch_events)


def run_once(cell, **session_kw):
    session = harness.Session(cell, **session_kw)
    win = session.window(SEED, seconds=0.05)
    numbers = session.check(win)
    ok, table = check.verdict(numbers, cell.limits)
    return ok and harness.failed_events(win) == 0, table


def alter_answer(compiled):
    """Event 0's counts are one higher than the program computed."""

    def sim(keys, batch):
        out = compiled(keys, batch)
        return out._replace(adc=out.adc.at[0].add(1))

    return sim


def drop_half_batch(compiled):
    """The batch's second half gets the first half's outputs."""

    def sim(keys, batch):
        out = compiled(keys, batch)
        half = out.adc.shape[0] // 2

        def fill(x):
            return x.at[half:].set(x[:x.shape[0] - half])

        return jax.tree.map(fill, out)

    return sim


CELLS = ["uboone-u.tracks100k", "uboone-u-recon.tracks100k"]


@pytest.mark.parametrize("name", CELLS)
def test_the_program_passes(name):
    ok, table = run_once(smoke_cell(name))
    assert ok, table


@pytest.mark.parametrize("name", CELLS)
def test_the_bfloat16_control_fails(name):
    ok, table = run_once(smoke_cell(name),
                         overrides={"charge_grid_strategy": "unfused_bf16"})
    assert not ok
    assert table["grid_err"]["value"] > table["grid_err"]["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_an_answer_altered_where_it_is_produced_fails(name):
    ok, table = run_once(smoke_cell(name), sim_hook=alter_answer)
    assert not ok
    assert table["adc_mismatch"]["value"] > table["adc_mismatch"]["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_half_of_the_batch_left_out_fails(name):
    ok, table = run_once(smoke_cell(name), sim_hook=drop_half_batch)
    assert not ok
    assert table["grid_err"]["value"] > table["grid_err"]["limit"]


def test_the_compared_batch_is_drawn_from_the_seed():
    picks = {check.sample_batch(s, 10) for s in range(3_000_000_000,
                                                      3_000_000_040)}
    assert len(picks) > 3 and picks <= set(range(10))
    assert check.sample_batch(SEED, 10) == check.sample_batch(SEED, 10)


def test_reference_hits_follow_the_stated_capacities():
    """Runs above threshold, the first ``max_hits_per_wire`` kept per wire
    and the first ``max_hits`` stored wire-major; every run counted."""
    import numpy as np

    from bench import reference

    decon = np.zeros((3, 12))
    decon[0, [1, 2, 5, 7, 8, 11]] = [600, 900, 700, 800, 800, 650]
    decon[2, 3:6] = [1000, 2000, 1000]
    sizes = {"hit_threshold": 500.0, "max_hits_per_wire": 3, "max_hits": 3}
    hits = reference.find_hits(decon, sizes)
    assert hits.n_found == 5
    assert hits.wire.tolist() == [0, 0, 0]
    assert hits.charge.tolist() == [1500.0, 700.0, 1600.0]
    assert hits.tick[0] == pytest.approx((600 * 1 + 900 * 2) / 1500)


def test_reference_convolution_matches_the_direct_sum():
    import numpy as np

    from bench import reference

    rng = np.random.default_rng(0)
    grid = rng.random((9, 14))
    kernel = rng.random((5, 4))
    got = reference.convolve(grid, kernel)
    want = np.zeros_like(grid)
    for w in range(9):
        for t in range(14):
            for i in range(5):
                for j in range(4):
                    src_w, src_t = w - (i - 2), t - j
                    if 0 <= src_w < 9 and 0 <= src_t < 14:
                        want[w, t] += kernel[i, j] * grid[src_w, src_t]
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)

