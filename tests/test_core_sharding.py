"""The event-axis sharding helpers (``repro.core.sharding``): logical dim
names -> PartitionSpecs, and the mesh context ``shard_events`` and the
batched executor read."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, NamedSharding, PartitionSpec as P

from repro.core.sharding import (ACT_RULES, build_spec, current_act_rules,
                                 current_mesh, logical, named_sharding,
                                 use_mesh)
from repro.launch.mesh import make_mesh


def _mesh(**axes):
    """A mesh of the given axis sizes; building a spec reads only its
    shape, so it needs no devices."""
    return AbstractMesh(tuple(axes.values()), tuple(axes))


def test_the_rule_table_names_the_simulator_dims():
    assert set(ACT_RULES) == {"events", "depos", "wires", "ticks"}


@pytest.mark.parametrize("shape,names,mesh,want", [
    # the event axis takes both data axes when their product divides it
    ((8, 5), ("events", None), _mesh(pod=2, data=4), P(("pod", "data"), None)),
    # ... and drops the trailing one first when it does not
    ((6,), ("events",), _mesh(pod=2, data=4), P("pod")),
    # no axis divides the dim: replicated
    ((3, 4), ("events", None), _mesh(data=2), P(None, None)),
    # a single mesh axis is written bare, not as a 1-tuple
    ((4,), ("events",), _mesh(data=4), P("data")),
    # axes absent from the mesh are skipped
    ((4, 16), ("events", "wires"), _mesh(data=4), P("data", None)),
    # an axis one dim took is not reused by a later dim
    ((4, 8), ("events", "depos"), _mesh(data=2, model=2), P("data", "model")),
    # unnamed dims, unknown names and ``ticks`` stay replicated
    ((4, 8, 8), (None, "planes", "ticks"), _mesh(data=2, model=2),
     P(None, None, None)),
], ids=["tuple", "trailing-drop", "indivisible", "bare-axis", "absent-axis",
        "used-once", "replicated"])
def test_build_spec(shape, names, mesh, want):
    assert build_spec(shape, names, mesh, ACT_RULES) == want


def test_build_spec_without_a_mesh_is_replicated():
    assert build_spec((4, 8), ("events", "wires"), None, ACT_RULES) == P()


def test_off_mesh_helpers_are_identities():
    x = jnp.arange(6.0).reshape(2, 3)
    assert current_mesh() is None
    assert logical(x, ("events", None)) is x
    assert named_sharding((2, 3), ("events", None)) is None


def test_use_mesh_nests_and_restores_the_outer_mesh():
    outer = make_mesh((1,), ("data",))
    inner = make_mesh((1, 1), ("data", "model"))
    with use_mesh(outer):
        assert current_mesh() is outer
        with use_mesh(inner):
            assert current_mesh() is inner
        assert current_mesh() is outer
        with use_mesh(None):
            assert current_mesh() is None
        assert current_mesh() is outer
    assert current_mesh() is None


def test_use_mesh_restores_on_an_exception():
    rules = {"events": None}
    with pytest.raises(RuntimeError, match="inside"):
        with use_mesh(make_mesh((1,), ("data",)), rules):
            assert current_act_rules() is rules
            raise RuntimeError("inside")
    assert current_mesh() is None
    assert current_act_rules() is ACT_RULES


def test_logical_constrains_under_a_mesh():
    mesh = make_mesh((1,), ("data",))
    x = jnp.arange(8.0).reshape(4, 2)
    with use_mesh(mesh):
        sharding = named_sharding(x.shape, ("events", None))
        got = jax.jit(lambda v: logical(v, ("events", None)) * 2.0)(x)
    assert isinstance(sharding, NamedSharding)
    assert sharding.spec == P("data", None)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x) * 2.0)
