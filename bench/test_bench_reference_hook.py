"""A configuration may bring its own reference, whose planes may differ in
wire count; one that brings none is checked exactly as before.

The two-plane module (``testdata/reference_two_planes.py``, 96 and 128
wires x 512 ticks) stands for a deployment's ``bench/references/<module>``:
fed its own outputs as the program's, the check reads 0, and a fault
planted in one plane fails the cell's limits. The default reference is
pinned to the numbers it read before the check went plane by plane."""
import dataclasses
import types

import pytest

from bench import check, depogen, harness
from bench.test_bench_check import SEED, smoke_cell

RECON = "uboone-u-recon.tracks100k"


@pytest.fixture(scope="module")
def two_planes():
    """(module, sizes, event ids, references) of one batch of two events."""
    module = harness.load_module("testdata", "reference_two_planes",
                                 "reference module")
    sizes = smoke_cell(RECON).sizes
    ids = [4, 5]
    refs = [module.reference_event(SEED, ev, sizes, 256, "tracks", True)
            for ev in ids]
    return module, sizes, ids, refs


def compare(two_planes, batch):
    module, sizes, ids, _ = two_planes
    return check.compare_batch(batch, ids, SEED, sizes, 256, "tracks", True,
                               module)


def program(two_planes):
    module, sizes, _, refs = two_planes
    return module.program_batch(refs, sizes["max_hits"])


def test_the_planes_differ_in_wire_count(two_planes):
    _, sizes, _, refs = two_planes
    for ref in refs:
        assert [a.shape for a in ref.adc] == [(96, sizes["num_ticks"]),
                                              (128, sizes["num_ticks"])]
        assert all(len(h.wire) > 0 for h in ref.hits)


def test_its_own_outputs_read_zero(two_planes):
    numbers = compare(two_planes, program(two_planes))
    assert numbers == {"grid_err": 0.0, "signal_err": 0.0,
                       "adc_mismatch": 0.0, "decon_err": 0.0,
                       "hit_mismatch": 0.0}


def test_one_plane_read_one_count_high_fails(two_planes):
    """Plane 1's signal 1/adc_per_electron electrons higher, so each of its
    counts one higher, as the program would digitize it."""
    _, sizes, _, _ = two_planes
    batch = program(two_planes)
    batch["signal"][:, 96:] += 1.0 / sizes["adc_per_electron"]
    batch["adc"][:, 96:] += 1
    ok, table = check.verdict(compare(two_planes, batch),
                              harness.load_cell(RECON).limits)
    assert not ok
    for name in ("adc_mismatch", "signal_err"):
        assert table[name]["value"] > table[name]["limit"], table
    assert table["grid_err"]["value"] == 0.0


def test_one_plane_losing_its_hits_fails(two_planes):
    batch = program(two_planes)
    batch["hits"]["mask"][:, 0] = False
    ok, table = check.verdict(compare(two_planes, batch),
                              harness.load_cell(RECON).limits)
    assert not ok
    assert table["hit_mismatch"]["value"] > table["hit_mismatch"]["limit"]
    assert table["adc_mismatch"]["value"] == 0.0


def test_a_config_naming_a_missing_reference_is_refused():
    cell = smoke_cell(RECON)
    cell = dataclasses.replace(cell, config=dict(cell.config,
                                                 reference="no_such_module"))
    with pytest.raises(harness.Refused,
                       match="bench/references/no_such_module.py"):
        harness.build_config(cell)


def test_a_config_naming_no_reference_is_checked_by_the_default():
    cell = smoke_cell(RECON)
    assert "reference" not in cell.config
    assert cell.reference is check
    assert check.GENERATORS is depogen.GENERATORS


def test_the_traffic_generator_is_looked_up_in_the_reference(monkeypatch):
    monkeypatch.setattr(harness, "load_reference",
                        lambda config: types.SimpleNamespace(GENERATORS={}))
    with pytest.raises(harness.Refused, match="unknown generator 'tracks'"):
        harness.build_config(smoke_cell(RECON))


def test_the_tracks_generator_refuses_other_track_lengths():
    cell = smoke_cell(RECON)
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                  depos_per_track=256))
    with pytest.raises(harness.Refused,
                       match="the program's stream draws tracks of 512 "
                             "depos only"):
        harness.build_config(cell)
    assert depogen.tracks_refusal(harness.load_cell(RECON).traffic) is None


@pytest.mark.parametrize("planes", [1, 3])
def test_the_program_inputs_come_from_its_packing(planes):
    """Keys (E,) and depo leaves (E, N) for one plane, (E, P, N) for
    several, at the cell's full size."""
    import jax

    cell = harness.load_cell(RECON)
    session = harness.Session.__new__(harness.Session)
    session.cell = cell
    session.cfg = dataclasses.replace(harness.build_config(cell),
                                      num_planes=planes)
    keys, batch = session._specs()
    e, n = cell.batch_events, cell.traffic["depos_per_event"]
    assert keys.shape == (e,) and keys.dtype == jax.random.key(0).dtype
    lead = (e,) if planes == 1 else (e, planes)
    for f in ("wire", "tick", "sigma_w", "sigma_t", "charge"):
        leaf = getattr(batch, f)
        assert (leaf.shape, str(leaf.dtype)) == (lead + (n,), "float32")
    assert (batch.n_depos.shape, str(batch.n_depos.dtype)) == ((e,), "int32")


def _session_numbers(cell):
    session = harness.Session(cell)
    win = session.window(SEED, seconds=0.0)  # two batches, whatever the clock
    return session.check(win)


#: what the check read at smoke size on SEED before it went plane by plane
#: (float.hex): the default reference with one plane, bit for bit
ONE_PLANE = {
    "uboone-u.tracks100k": {
        "grid_err": "0x1.8e72b86d93248p-19",
        "signal_err": "0x1.f87ce593eea6fp-17",
        "adc_mismatch": "0x1.0000000000000p-14"},
    RECON: {
        "grid_err": "0x1.8e72b86d93248p-19",
        "signal_err": "0x1.f87ce593eea6fp-17",
        "adc_mismatch": "0x1.0000000000000p-14",
        "decon_err": "0x1.22a4cf45200e3p-8",
        "hit_mismatch": "0x0.0p+0"},
}


@pytest.mark.parametrize("name", sorted(ONE_PLANE))
def test_the_cells_read_bit_for_bit_as_before(name):
    numbers = _session_numbers(smoke_cell(name))
    assert {k: float(v).hex() for k, v in numbers.items()} == ONE_PLANE[name]


def test_three_equal_planes_read_as_before():
    """The default reference's own multi-plane path: norms summed plane by
    plane now, over the stacked planes before, so to rounding."""
    cell = smoke_cell(RECON)
    cell = dataclasses.replace(cell, config=dict(
        cell.config, sizes=dict(cell.sizes, num_planes=3),
        overrides=dict(cell.config["overrides"], num_planes=3)))
    before = {"grid_err": "0x1.daeb68f3059d9p-19",
              "signal_err": "0x1.ceb63318a3ea6p-17",
              "adc_mismatch": "0x1.0000000000000p-15",
              "decon_err": "0x1.a69eebb6e9c50p-9",
              "hit_mismatch": "0x0.0p+0"}
    numbers = _session_numbers(cell)
    assert set(numbers) == set(before)
    for k, v in before.items():
        assert numbers[k] == pytest.approx(float.fromhex(v), rel=1e-12,
                                           abs=0.0), k
