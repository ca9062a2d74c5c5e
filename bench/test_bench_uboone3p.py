"""The three-plane cell, ``uboone3p-recon.tracks100k``, at a size a test
run holds: the registry entry's smoke size, 96 / 96 / 128 wires x 512
ticks, 256 depos per event, on the CPU.

The program, run through the harness, passes its configuration's own
reference (``bench/references/uboone3p.py``) under the cell's limits; the
lower-precision control (bfloat16 patches) and a planted fault fail. The
reference's frozen generator and projection give the program's depos bit
for bit, and the per-plane readers read the plane-kind scopes."""
import jax
import numpy as np
import pytest

from bench import check, harness, tracereduce
from bench.test_bench_check import SEED, alter_answer, run_once, smoke_cell

NAME = "uboone3p-recon.tracks100k"


@pytest.fixture(scope="module")
def cell():
    return smoke_cell(NAME)


def test_the_cell_names_its_own_reference(cell):
    assert cell.config["reference"] == "uboone3p"
    assert cell.reference is not check
    assert cell.sizes["plane_wires"] == [96, 96, 128]
    full = harness.load_cell(NAME)
    assert full.sizes["plane_wires"] == [2400, 2400, 3456]
    assert full.config["reduced"] == []
    assert (full.batch_events, full.recon) == (2, True)


def test_the_program_passes(cell):
    ok, table = run_once(cell)
    assert ok, table


def test_the_bf16_control_fails(cell):
    ok, table = run_once(cell, overrides={
        "charge_grid_strategy": "unfused_bf16"})
    assert not ok
    assert table["grid_err"]["value"] > table["grid_err"]["limit"], table


def test_an_altered_answer_fails(cell):
    ok, table = run_once(cell, sim_hook=alter_answer)
    assert not ok
    assert table["adc_mismatch"]["value"] > table["adc_mismatch"]["limit"]


def test_the_frozen_generator_and_projection_give_the_programs_depos(cell):
    from repro.core.depo import generate_plane_depos

    cfg = harness.build_config(cell)
    ref = cell.reference
    key = jax.random.fold_in(jax.random.key(SEED), 3)
    prog = generate_plane_depos(key, cfg)
    phys = ref.GENERATORS["tracks"].draw(key, cfg.num_depos, cell.sizes)
    phys = {f: np.asarray(getattr(phys, f)) for f in phys._fields}
    for p, (_, angle, pitch, wires) in enumerate(ref.planes(cell.sizes)):
        wire = ref.project(phys["y"], phys["z"], angle, pitch, wires,
                           cell.sizes)
        np.testing.assert_array_equal(np.asarray(prog.wire[p]), wire)


def test_the_view_splits_at_the_planes_wire_counts(cell):
    t = cell.sizes["num_ticks"]
    adc = np.arange(2 * 320 * t).reshape(2, 320, t)
    hits = {"mask": np.zeros((2, 3, 8), bool)}
    view = cell.reference.event_view({"adc": adc, "hits": hits}, 1,
                                     cell.sizes)
    assert [a.shape[0] for a in view["adc"]] == [96, 96, 128]
    np.testing.assert_array_equal(view["adc"][2], adc[1, 192:])
    assert len(view["hits"]) == 3 and view["hits"][0]["mask"].shape == (8,)


def test_the_plane_readers_read_the_plane_kind_scopes():
    ops = [("a", 0, 10), ("b", 10, 30), ("c", 30, 36), ("d", 40, 50)]
    kinds = {"a": "jit(run)/vmap(convolve)/induction/mul",
             "b": "jit(run)/vmap(hit_find)/induction/while",
             "c": "jit(run)/vmap(noise)/collection/mul",
             "d": "jit(run)/vmap(charge_grid)/scatter-add"}
    rec = tracereduce.Record(window=(0, 100), ops={0: ops}, spans=[],
                             kinds=kinds, events=2)
    read = {n: harness.load_reader(n).read(rec)
            for n in ("collection_plane_ms_per_event",
                      "induction_planes_ms_per_event")}
    assert read["induction_planes_ms_per_event"] == pytest.approx(15e-6)
    assert read["collection_plane_ms_per_event"] == pytest.approx(3e-6)
    plain = tracereduce.Record(window=(0, 100), ops={0: ops[3:]}, spans=[],
                               kinds=kinds, events=2)
    assert harness.load_reader("collection_plane_ms_per_event").read(
        plain) is None
