"""Layer layout (models/layout.py): the one description of each kind's
layer grouping, from which the params, the ring cache and the paged
cache are built and which every phase walks."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, get_arch
from repro.configs.base import ARCH_KINDS
from repro.models import build_model, layout
from repro.models import transformer as tfm
from repro.models.common import split_tree

CFGS = {name: get_arch(name).reduced() for name in sorted(ARCHS)}
# a hybrid with one (rec, rec, attn) group and a 2-layer recurrent tail
CFGS["recurrentgemma-9b@5"] = get_arch("recurrentgemma-9b").reduced(
    num_layers=5)
# the enc-dec kind no registered arch has
CFGS["encdec"] = dataclasses.replace(get_arch("whisper-small").reduced(),
                                     kind="encdec")
NAMES = sorted(CFGS)
LAYER_TREES = ("layers", "groups", "tail", "enc_layers")


def _segments(cfg):
    return layout.encoder_layout(cfg) + layout.layer_layout(cfg)


def _shape(a):
    return tuple(a.shape)


def _is_shape(x):
    return isinstance(x, tuple) and all(isinstance(d, int) for d in x)


def _one_layer(cfg, role):
    """Shapes of one layer of ``role`` as init makes it."""
    return jax.tree.map(_shape, jax.eval_shape(
        lambda k: split_tree(tfm._init_layer(role, k, cfg))[0],
        jax.random.PRNGKey(0)))


@pytest.mark.parametrize("name", NAMES)
def test_build_gives_init_model_tree(name):
    """Each layer tree of the params: one layer's shapes per role, in the
    layout's sub-keys, stacked ``n`` deep where the segment is scanned."""
    cfg = CFGS[name]
    params, _ = build_model(cfg).abstract_params()
    segs = _segments(cfg)
    want = layout.build(
        segs, lambda role, _k: _one_layer(cfg, role),
        lambda body, _k, n: jax.tree.map(lambda s: (n,) + s, body(None),
                                         is_leaf=_is_shape))
    got = {k: jax.tree.map(_shape, v)
           for k, v in params.items() if k in LAYER_TREES}
    assert got == want
    assert set(want) == {s.key for s in segs}


def _same_structure(tree, axes):
    flat_ax, ax_def = jax.tree_util.tree_flatten(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    leaves, tree_def = jax.tree_util.tree_flatten(tree)
    assert tree_def == ax_def
    for leaf, ax in zip(leaves, flat_ax):
        assert leaf.ndim == len(ax), (leaf.shape, ax)


@pytest.mark.parametrize("name", NAMES)
def test_cache_trees_have_their_axes_structure(name):
    cfg = CFGS[name]
    model = build_model(cfg)
    keys = {s.key for s in layout.layer_layout(cfg)}
    ring = model.abstract_cache(2, 16, jnp.float32)
    assert set(ring) == keys
    _same_structure(ring, model.cache_axes())
    if cfg.kind in layout.FRONTEND_KINDS:
        with pytest.raises(ValueError):
            model.abstract_paged_cache(2, 9, 4, jnp.float32)
        return
    paged = model.abstract_paged_cache(2, 9, 4, jnp.float32)
    assert set(paged) == keys
    _same_structure(paged, model.paged_cache_axes())


@pytest.mark.parametrize("with_cache", [False, True])
@pytest.mark.parametrize("name", NAMES)
def test_run_visits_every_layer_once(name, with_cache):
    """The walk runs each layer once, in its role: ``num_layers`` decoder
    layers plus ``enc_num_layers`` encoder layers."""
    cfg = CFGS[name]
    segs = _segments(cfg)
    roles = sorted(set(layout.layer_roles(segs)))
    tree = layout.build(
        segs, lambda role, _k: jnp.zeros(()),
        lambda body, _k, n: jax.tree.map(
            lambda l: jnp.zeros((n,) + l.shape), body(None)))

    def count(role):
        r = roles.index(role)
        if with_cache:   # each layer adds one to its own slab of the cache
            return lambda lp, x, c, i: (
                x.at[r].add(1), layout.write(c, i, layout.read(c, i) + 1))
        return lambda lp, x: (x.at[r].add(1), None)

    x, outs = layout.run(segs, tree, jnp.zeros(len(roles), jnp.int32),
                         {r: count(r) for r in roles},
                         cache=tree if with_cache else None)
    seen = dict(zip(roles, x.tolist()))
    assert sum(seen.values()) == cfg.num_layers + (
        cfg.enc_num_layers if layout.encoder_layout(cfg) else 0)
    if with_cache:   # the new cache comes back in the tree's structure,
        # each layer's slab written once, by that layer
        assert (jax.tree_util.tree_structure(outs)
                == jax.tree_util.tree_structure(tree))
        assert all(bool((leaf == 1).all()) for leaf in jax.tree.leaves(outs))
    if cfg.kind == "hybrid":
        assert seen["local_attn"] == cfg.num_layers // 3
    if cfg.kind == "mamba_hybrid":
        assert seen["attention"] == cfg.layer_types.count("attention")
    if cfg.kind == "moe":
        assert seen["moe"] == cfg.num_layers // cfg.moe_every


# (page pools, prefix sharing) per kind, pinned: a paged kind with an
# attention layer keeps page pools, one whose layers all attend shares
# prefix pages; the frontend kinds are not paged
PAGED_STATE = {"dense": (True, True), "moe": (True, True),
               "ssm": (False, False), "hybrid": (True, False),
               "mamba_hybrid": (True, False), "vlm": (False, False),
               "encdec": (False, False), "audio": (False, False)}


@pytest.mark.parametrize("name", NAMES)
def test_derived_paged_state_matches_the_kind_lists(name):
    cfg = CFGS[name]
    assert (layout.has_page_pools(cfg),
            layout.shares_prefix(cfg)) == PAGED_STATE[cfg.kind]
    # the stacked trees: the layer trees that are dicts, not lists
    params, _ = build_model(cfg).abstract_params()
    assert layout.stacked_keys(layout.layer_layout(cfg)) == tuple(
        k for k in ("layers", "groups", "tail")
        if isinstance(params.get(k), dict))


def test_the_cases_cover_every_kind():
    assert set(PAGED_STATE) == set(ARCH_KINDS)
    assert set(layout.FRONTEND_KINDS) <= set(ARCH_KINDS)
    assert {cfg.kind for cfg in CFGS.values()} == set(ARCH_KINDS)


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_depth_is_the_published_scan_length(name):
    """The scan length ``accum_steps_for`` budgets, at published depth:
    an interleaved MoE's groups, a hybrid's groups plus its tail, an
    enc-dec's encoder plus decoder layers, else one per layer."""
    cfg = get_arch(name)
    scan_len = {"llama4-maverick-400b-a17b": 48 // 2,
                "recurrentgemma-9b": 38 // 3 + 2,
                "whisper-small": 12 + 12}.get(name, cfg.num_layers)
    assert layout.depth(_segments(cfg)) == scan_len
