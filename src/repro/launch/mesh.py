"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state; `dryrun.py` sets XLA_FLAGS *before* importing anything.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the in-model
    ``hint`` pins (``with_sharding_constraint``) may only name Auto
    axes, and ``make_mesh`` otherwise makes them Explicit."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, pods: int = 2):
    """Single pod: 256 chips as (data=16, model=16).
    Multi-pod: ``pods`` pods of 256 chips as (pod, data=16, model=16) —
    the default 2 pods is the 512-chip production target; pods=40 is the
    10,240-chip scale-out lowering check (``--mesh multipod10k``)."""
    shape = (pods, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def chips_in(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n


def make_serve_mesh(spec: str = "host"):
    """Serving mesh over the devices of THIS process (``launch/serve.py
    --mesh``; the production 512-chip meshes stay in
    :func:`make_production_mesh`).

    ``spec``:
      * ``"host"``  — all local devices tensor-parallel: (data=1, model=n)
      * ``"data"``  — all local devices data-parallel:   (data=n, model=1)
      * ``"AxB"``   — explicit (data=A, model=B), e.g. ``"2x4"``

    Axes are always ``("data", "model")`` so the serve rule tables
    resolve identically across specs (absent/size-1 axes no-op).
    """
    n = len(jax.devices())
    if spec == "host":
        shape = (1, n)
    elif spec == "data":
        shape = (n, 1)
    else:
        try:
            d, m = (int(x) for x in spec.split("x"))
        except ValueError:
            raise ValueError(
                f"mesh spec {spec!r}: expected 'host', 'data', or 'AxB'")
        if d * m != n:
            raise ValueError(
                f"mesh spec {spec!r} wants {d * m} devices, have {n}")
        shape = (d, m)
    return auto_mesh(shape, ("data", "model"))
