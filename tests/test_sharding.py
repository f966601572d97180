"""repro.dist sharding layer: rule resolution, mesh-aware dropping,
duplicate-mesh-axis conflicts, overrides, and hint/drop_hint_axes
semantics (on small host-device meshes)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import (
    ShardingRules, drop_hint_axes, hint, resolve_hint_spec,
)
from repro.launch.mesh import auto_mesh

RULES = ShardingRules((
    ("batch", ("pod", "data")),
    ("replica", ("pod", "data")),
    ("embed", ("pod", "data")),
    ("vocab", "model"),
    ("ffn", "model"),
    ("layers", None),
))


@pytest.fixture(scope="module")
def mesh3():
    """(pod=1, data=1, model=1) — axis names matter, sizes don't."""
    return auto_mesh((1, 1, 1), ("pod", "data", "model"))


@pytest.fixture(scope="module")
def mesh_dm():
    return auto_mesh((1, 1), ("data", "model"))


def test_spec_basic(mesh3):
    assert RULES.spec(("batch", None, None), mesh3) == \
        P(("pod", "data"), None, None)
    assert RULES.spec(("layers", "embed", "vocab"), mesh3) == \
        P(None, ("pod", "data"), "model")


def test_spec_drops_axes_missing_from_mesh(mesh_dm):
    # same table serves the single-pod mesh: "pod" silently dropped
    assert RULES.spec(("batch", "vocab"), mesh_dm) == P("data", "model")


def test_spec_duplicate_mesh_axis_leftmost_wins(mesh3):
    # replica claims (pod, data); embed's (pod, data) and a second
    # "model" dim must not re-claim — a mesh axis shards ONE dim only
    spec = RULES.spec(("replica", "embed", "vocab", "ffn"), mesh3)
    assert spec == P(("pod", "data"), None, "model", None)


def test_spec_unknown_logical_axis_raises(mesh3):
    with pytest.raises(KeyError):
        RULES.spec(("no_such_axis",), mesh3)


def test_duplicate_rule_rejected():
    with pytest.raises(ValueError):
        ShardingRules((("a", None), ("a", "model")))


def test_with_overrides_preserves_order_and_appends(mesh3):
    over = RULES.with_overrides(embed=None, cache_seq="model")
    assert over.logical_axes()[:6] == RULES.logical_axes()
    assert over.logical_axes()[-1] == "cache_seq"
    assert over.mesh_axes("embed") == ()
    assert over.mesh_axes("cache_seq") == ("model",)
    # original untouched (immutability)
    assert RULES.mesh_axes("embed") == ("pod", "data")
    assert over.spec(("batch", "cache_seq"), mesh3) == \
        P(("pod", "data"), "model")


def test_hint_noop_off_mesh():
    x = jnp.ones((4, 8))
    assert hint(x, ("pod", "data"), "model") is x


def test_hint_arity_check():
    with pytest.raises(ValueError):
        hint(jnp.ones((4, 8)), ("pod", "data"))


def test_hint_spec_under_mesh(mesh3):
    assert resolve_hint_spec((("pod", "data"), "model"), mesh3) == \
        P(("pod", "data"), "model")
    # duplicate-claim: later dim must not re-claim "model"
    assert resolve_hint_spec(("model", "model"), mesh3) == P("model", None)


def test_hint_spec_filters_missing_axes(mesh_dm):
    assert resolve_hint_spec((("pod", "data"), "model"), mesh_dm) == \
        P("data", "model")


def test_drop_hint_axes_masks_and_nests(mesh3):
    x = jnp.ones((4, 8))
    spec = (("pod", "data"), "model")
    with drop_hint_axes(("pod",)):
        assert resolve_hint_spec(spec, mesh3) == P("data", "model")
        with drop_hint_axes(("data",)):   # inner ADDS to outer
            assert resolve_hint_spec(spec, mesh3) == P(None, "model")
        # outer drop set restored
        assert resolve_hint_spec(spec, mesh3) == P("data", "model")
    assert resolve_hint_spec(spec, mesh3) == P(("pod", "data"), "model")
    # all-dropped hint is a no-op even under an active mesh
    with mesh3:
        with drop_hint_axes(("pod", "data", "model")):
            assert hint(x, ("pod", "data"), "model") is x


def test_hint_inside_jit(mesh3):
    x = jnp.ones((4, 8))
    with mesh3:
        y = jax.jit(lambda a: hint(a, ("pod", "data"), "model") * 2)(x)
    np.testing.assert_allclose(np.asarray(y), 2 * np.ones((4, 8)))


def test_tthf_scale_rule_table_resolves(mesh3):
    """The scale-mode table from core/distributed.py resolves for every
    declared logical axis on the multi-pod mesh."""
    from repro.core.distributed import TTHF_PARAM_RULES
    rules = ShardingRules(TTHF_PARAM_RULES)
    for name in rules.logical_axes():
        spec = rules.spec(("replica", name), mesh3)
        assert spec[0] == ("pod", "data")
        # replica already claimed (pod, data): no other axis may re-use
        assert spec[1] in (None, "model")
