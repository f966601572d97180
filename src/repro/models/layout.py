"""Layer layout: the one description of how a kind groups its layers.

A layout is a tuple of :class:`Segment`, one per top-level tree of
layers, in the order the layers run. The params, the ring cache and the
paged cache all hold one tree per segment under the segment's key, and
every phase (train forward, prefill, decode, chunked prefill, paged
decode) walks the same layout with its own per-role bodies:

* ``layers``: one scanned stack (dense, vlm, ssm, enc-dec decoder,
  ``moe`` with ``moe_every == 1``);
* ``groups``: a scanned stack of repeating units, ``dense_{i}`` + ``moe``
  (``moe_every``) or ``rec_{i}`` + ``attn`` (``hybrid``), plus the
  hybrid's ``tail`` stack of the recurrent layers left over;
* ``layers`` as a list: ``mamba_hybrid``'s layers unrolled in their
  published ``layer_types`` order (the mixers differ, so no stack
  holds them);
* ``enc_layers``: the enc-dec encoder, a layout of its own
  (:func:`encoder_layout`) that runs on the frontend's frames.

A role names what one layer is: ``attn`` (self-attention + MLP),
``moe`` (self-attention + MoE FFN), ``local_attn`` (the hybrid's
sliding-window attention), ``rec`` (RG-LRU), ``ssm`` (Mamba-2),
``enc`` / ``cross`` (enc-dec encoder / decoder with cross-attention),
and ``mamba`` / ``attention`` (``mamba_hybrid``'s mixers, each followed
by its expert FFN). Adding a kind is one branch in :func:`layer_layout`
plus a body per new role in each phase.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

# kinds whose requests carry frontend inputs (patch or frame embeddings):
# the request schedulers and the paged cache serve every other kind
FRONTEND_KINDS = ("vlm", "encdec", "audio")
# roles whose layer keeps K/V: a page pool each in the paged cache
ATTENTION_ROLES = frozenset({"attn", "moe", "local_attn", "attention"})


class Segment(NamedTuple):
    """One tree of layers. ``roles`` is ``((sub_key, role), ...)``: for a
    scanned stack, the layers of one body in order (``sub_key`` None when
    the body is a single layer's tree); for an unrolled list, one entry
    per layer, ``sub_key`` its index."""
    key: str
    n: int
    roles: tuple
    scanned: bool = True


def layer_layout(cfg) -> tuple[Segment, ...]:
    """The decoder stack of ``cfg``, in the order its layers run."""
    kind = cfg.kind
    if kind in ("dense", "vlm"):
        return (Segment("layers", cfg.num_layers, ((None, "attn"),)),)
    if kind in ("encdec", "audio"):
        return (Segment("layers", cfg.num_layers, ((None, "cross"),)),)
    if kind == "ssm":
        return (Segment("layers", cfg.num_layers, ((None, "ssm"),)),)
    if kind == "moe":
        k = cfg.moe_every
        if k == 1:
            return (Segment("layers", cfg.num_layers, ((None, "moe"),)),)
        roles = tuple((f"dense_{i}", "attn") for i in range(k - 1))
        return (Segment("groups", cfg.num_layers // k,
                        roles + (("moe", "moe"),)),)
    if kind == "hybrid":
        period = hybrid_period(cfg)
        n, rem = divmod(cfg.num_layers, period)
        roles = tuple((f"rec_{i}", "rec") for i in range(period - 1))
        segs = ()
        if n:
            segs += (Segment("groups", n, roles + (("attn", "local_attn"),)),)
        if rem:
            segs += (Segment("tail", rem, ((None, "rec"),)),)
        return segs
    if kind == "mamba_hybrid":
        return (Segment("layers", cfg.num_layers,
                        tuple(enumerate(cfg.layer_types)), scanned=False),)
    raise ValueError(kind)


def hybrid_period(cfg) -> int:
    """A hybrid's layers per group: recurrent layers, then one attention."""
    return cfg.local_attn_every or 3


def encoder_layout(cfg) -> tuple[Segment, ...]:
    """The enc-dec encoder stack (empty for every other kind)."""
    if cfg.kind in ("encdec", "audio"):
        return (Segment("enc_layers", cfg.enc_num_layers, ((None, "enc"),)),)
    return ()


def layer_roles(segs) -> list[str]:
    return [role for seg in segs for _, role in seg.roles]


def depth(segs) -> int:
    """Bodies the walk runs: a scanned segment's stack length, or an
    unrolled segment's layers."""
    return sum(seg.n for seg in segs)


def stacked_keys(segs) -> tuple[str, ...]:
    """The trees ``jax.lax.scan`` consumes."""
    return tuple(seg.key for seg in segs if seg.scanned)


def has_page_pools(cfg) -> bool:
    """Does the paged cache keep K/V page pools (an attention layer)?"""
    return cfg.kind not in FRONTEND_KINDS and any(
        r in ATTENTION_ROLES for r in layer_roles(layer_layout(cfg)))


def shares_prefix(cfg) -> bool:
    """Is a request's whole paged state K/V, so that a prompt prefix's
    pages can be shared? A recurrent layer's state cannot be borrowed."""
    return cfg.kind not in FRONTEND_KINDS and all(
        r in ATTENTION_ROLES for r in layer_roles(layer_layout(cfg)))


# ---------------------------------------------------------------------------
# build and run
# ---------------------------------------------------------------------------

def build(segs, make: Callable, stack: Callable, keys=None) -> dict:
    """The tree of the segments ``segs``: ``make(role, key)`` makes one layer's
    tree, ``stack(body, key, n)`` stacks ``n`` bodies made by
    ``body(key)``. ``keys``: one PRNG key per segment, split per body
    and per layer as the stack and the body need (None: every maker
    gets None)."""
    tree = {}
    for i, seg in enumerate(segs):
        key = None if keys is None else keys[i]
        if seg.scanned:
            tree[seg.key] = stack(
                functools.partial(_make_body, seg.roles, make), key, seg.n)
        else:
            tree[seg.key] = [make(role, k) for (_, role), k in
                             zip(seg.roles, _split(key, seg.n))]
    return tree


def _split(key, n):
    return [None] * n if key is None else jax.random.split(key, n)


def _make_body(roles, make, key):
    if roles[0][0] is None:
        return make(roles[0][1], key)
    return {sub: make(role, k)
            for (sub, role), k in zip(roles, _split(key, len(roles)))}


def run(segs, params, x, bodies: dict, cache: Optional[dict] = None,
        remat: bool = False):
    """Walk the segments ``segs`` over ``x``: one ``jax.lax.scan`` per scanned
    segment over ``params[key]``, the unrolled segment in a Python loop,
    one ``bodies[role](lp, x)`` call per layer, each returning
    ``(x, out)``. ``remat`` wraps each scanned body in ``jax.checkpoint``.

    With ``cache``, a scanned segment carries its stacked cache tree
    ``cache[key]`` through the scan next to ``x``, so a caller that
    donates the cache has it updated in place: each call is
    ``bodies[role](lp, x, c, i)`` with the whole stacked tree ``c`` and
    the layer index ``i``, and returns ``(x, c)`` with only layer ``i``
    rewritten (:func:`read`, :func:`write`, :func:`read_pages`,
    :func:`write_rows`). An unrolled layer gets its own tree as a stack
    of one, with ``i`` 0.

    Returns ``(x, {key: outs})``: without a cache the per-layer outputs
    (MoE aux or None) in the tree structure of the segment's params;
    with one, the new cache.
    """
    outs = {}
    for seg in segs:
        p = params[seg.key]
        c = None if cache is None else cache[seg.key]
        if not seg.scanned:
            outs[seg.key] = []
            for j, role in seg.roles:
                if c is None:
                    x, out = bodies[role](p[j], x)
                else:
                    one = jax.tree.map(lambda a: a[None], c[j])
                    x, out = bodies[role](p[j], x, one, 0)
                    out = jax.tree.map(lambda a: a[0], out)
                outs[seg.key].append(out)
            continue
        body = functools.partial(_run_body, seg.roles, bodies)
        fn = jax.checkpoint(body) if remat else body
        if c is None:
            x, outs[seg.key] = jax.lax.scan(lambda xx, lp: fn(lp, xx), x, p)
            continue

        def step(carry, lp_i, fn=fn):
            return fn(lp_i[0], *carry, lp_i[1]), None
        (x, outs[seg.key]), _ = jax.lax.scan(
            step, (x, c), (p, jnp.arange(seg.n, dtype=jnp.int32)))
    return x, outs


def _call(body, lp, x, c, i):
    return body(lp, x) if c is None else body(lp, x, c, i)


def _run_body(roles, bodies, lp, x, c=None, i=None):
    if roles[0][0] is None:
        return _call(bodies[roles[0][1]], lp, x, c, i)
    outs = {}
    for sub, role in roles:
        x, outs[sub] = _call(bodies[role], lp[sub], x,
                             None if c is None else c[sub], i)
    return x, outs


# ---------------------------------------------------------------------------
# one layer of a carried cache
# ---------------------------------------------------------------------------

def _start(leaf, i, slot):
    lead = (i,) if slot is None else (i, slot)
    return lead, lead + (0,) * (leaf.ndim - len(lead))


def read(tree, i, slot=None):
    """Layer ``i`` of a stacked cache tree: each leaf's slab, or with
    ``slot`` its lane ``slot`` (kept as an axis of 1). One dynamic slice
    per leaf."""
    def one(leaf):
        lead, start = _start(leaf, i, slot)
        return jax.lax.dynamic_slice(
            leaf, start, (1,) * len(lead) + leaf.shape[len(lead):])[0]
    return jax.tree.map(one, tree)


def write(tree, i, new, slot=None):
    """``tree`` with ``new`` written where :func:`read` reads: one
    dynamic-update-slice per leaf, in place when the tree is a donated
    scan carry (a ``where`` that makes ``new`` fuses into it)."""
    def one(leaf, val):
        _, start = _start(leaf, i, slot)
        return jax.lax.dynamic_update_slice(
            leaf, val.astype(leaf.dtype)[None], start)
    return jax.tree.map(one, tree, new)


def read_pages(pool, i, index):
    """Pages ``index`` of layer ``i`` of a stacked page pool
    ``(layers, num_pages, ...)``: one gather, ``pool[i, index]``. Slicing
    the layer out first would copy its whole pool."""
    return pool[i, index]


def write_rows(pool, i, flat, rows):
    """``pool`` with ``rows`` (B, ...) written at the flat page offsets
    ``flat`` (B,) (``page * page_size + offset``) of layer ``i``: one
    scatter of B rows."""
    L, n, ps = pool.shape[:3]
    f = pool.reshape((L, n * ps) + pool.shape[3:])
    f = f.at[i, flat].set(rows.astype(f.dtype))
    return f.reshape(pool.shape)
