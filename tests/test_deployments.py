"""The deployment registry (``repro.config``): the ids it lists, the
configurations it builds at full and smoke size, and the overrides the
launchers and the benchmark's configuration files apply to them."""
import dataclasses

import pytest

from repro.config import (LArTPCConfig, apply_overrides, face_extent_mm,
                          get_config, list_archs, plane_specs)

DEPLOYMENTS = ("lartpc-uboone", "lartpc-uboone3p")


def test_registry_lists_the_deployments():
    assert list_archs() == DEPLOYMENTS


def test_overrides():
    cfg = get_config("lartpc-uboone")
    cfg2 = apply_overrides(cfg, {"num_depos": "8", "fluctuate": "false"})
    assert cfg2.num_depos == 8 and cfg2.fluctuate is False
    assert cfg.num_depos == 100_000 and cfg.fluctuate is True  # untouched


def test_unknown_deployment_raises():
    with pytest.raises(KeyError, match="not-a-deployment"):
        get_config("not-a-deployment")


@pytest.mark.parametrize("smoke", [False, True], ids=["full", "smoke"])
@pytest.mark.parametrize("deployment", DEPLOYMENTS)
def test_every_deployment_builds(deployment, smoke):
    """A fresh, hashable config each call, whose plane geometry validates
    and spans a face of positive extent."""
    cfg = get_config(deployment, smoke=smoke)
    assert isinstance(cfg, LArTPCConfig)
    assert cfg == get_config(deployment, smoke=smoke)
    hash(cfg)
    specs = plane_specs(cfg)
    assert len(specs) == cfg.num_planes
    assert max(s.num_wires for s in specs) == cfg.num_wires
    y, z = face_extent_mm(cfg)
    assert y > 0.0 and z > 0.0


@pytest.mark.parametrize("field,value,want", [
    ("num_depos", "64", 64),
    ("noise_rms_adc", "2.5", 2.5),
    ("check_finite", "true", True),
    ("fluctuate", "0", False),
    ("scatter_strategy", "lane_rows", "lane_rows"),
    ("plane_types", ("collection",) * 3, ("collection",) * 3),
    ("num_wires", 96, 96),
], ids=["int", "float", "bool-true", "bool-false", "str", "tuple", "native"])
def test_apply_overrides_takes_each_field_type(field, value, want):
    """``--set`` strings parse to the field's type; other values pass
    through as given."""
    cfg = apply_overrides(get_config("lartpc-uboone", smoke=True),
                          {field: value})
    got = getattr(cfg, field)
    assert got == want and type(got) is type(want)
    others = {f.name for f in dataclasses.fields(cfg)} - {field}
    base = get_config("lartpc-uboone", smoke=True)
    assert all(getattr(cfg, f) == getattr(base, f) for f in others)


def test_an_unknown_field_is_refused():
    with pytest.raises(KeyError, match="num_anodes"):
        apply_overrides(get_config("lartpc-uboone", smoke=True),
                        {"num_anodes": "2"})
