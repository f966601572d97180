"""jit'd public wrappers for the Pallas kernels.

Off-TPU the kernels run in ``interpret=True`` mode (the kernel body
executes as traced jnp ops); on a real TPU backend they compile via
Mosaic. ``INTERPRET`` is auto-detected once per process by
``repro.kernels.runtime.default_interpret`` — a kernel module imported
directly (bypassing these wrappers) auto-detects the same way, so a TPU
caller can no longer silently run interpreted. Wrappers handle padding
and expose oracle-identical signatures so call-sites can swap
kernel <-> ref freely.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import fused_consensus_sgd as _fcs
from repro.kernels import fused_sgd as _fs
from repro.kernels import ssd_scan as _ss
from repro.kernels import ref
from repro.kernels.runtime import default_interpret

# Auto-detected: True off-TPU (interpret mode), False on real TPUs.
# Still assignable for tests/benches that force one mode.
INTERPRET = default_interpret()


def consensus_mix(z: jax.Array, V: jax.Array, gamma: jax.Array,
                  blk_m: int = 512) -> jax.Array:
    """D2D mixing via the unified engine's Pallas backend
    (``repro.core.mixing``; honors this module's INTERPRET flag)."""
    from repro.core import mixing
    return mixing.mix(z, V, gamma, backend="pallas", blk_m=blk_m)


def ssd_scan(x: jax.Array, dt: jax.Array, loga: jax.Array, B: jax.Array,
             C: jax.Array, chunk: int = 256):
    """Pads T to a chunk multiple, calls the kernel, trims."""
    T = x.shape[1]
    pad = (-T) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad)))
        loga = jnp.pad(loga, ((0, 0), (0, pad)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    y, h = _ss.ssd_scan(x, dt, loga, B, C, chunk=chunk, interpret=INTERPRET)
    return (y[:, :T], h) if pad else (y, h)


def fused_sgd(w: jax.Array, g: jax.Array, eta, weight_decay: float = 0.0
              ) -> jax.Array:
    return _fs.fused_sgd(w, g, eta, weight_decay=weight_decay,
                         interpret=INTERPRET)


def fused_consensus_sgd(w: jax.Array, g: jax.Array, W: jax.Array, eta,
                        weight_decay: float = 0.0) -> jax.Array:
    """Fused last-microstep SGD + W-mixing; w, g: (N, s, rows, 128),
    W: (N, s, s)."""
    return _fcs.fused_consensus_sgd(w, g, W, eta,
                                    weight_decay=weight_decay,
                                    interpret=INTERPRET)


__all__ = ["consensus_mix", "ssd_scan", "fused_sgd",
           "fused_consensus_sgd", "ref", "INTERPRET"]
