"""Mosaic compile guard: every Pallas kernel compiles for a described
TPU v5e (``v5e:2x2``) at real widths, with no chip attached.

Interpret-mode tests (``test_kernels.py``) cannot see what the TPU
compiler refuses: loads from non-VMEM/SMEM refs, blocks that break the
(8, 128) tiling rule, primitives Mosaic has no lowering for. Each test
here lowers one kernel against the topology's first device and asserts
the compiled HLO holds a ``tpu_custom_call``.

The topology is described inside a module-scoped fixture (never at
import): only one process may load the TPU compiler library, and test
collection must be identical across xdist workers.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch

os.environ.setdefault("TPU_LOG_DIR", "disabled")

MAMBA = get_arch("mamba2-370m")
QWEN = get_arch("qwen1.5-0.5b")
STARCODER = get_arch("starcoder2-3b")
REPLICAS, CLUSTER = 4, 2        # chip_smoke's TT-HF layout


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip land in the persistent cache but
    # cannot be read back without one: keep the cache off meanwhile
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def flat_spec():
    """mamba2-370m's fused-interval carrier layout (FlatParamSpec)."""
    from repro.core.distributed import FlatParamSpec
    from repro.models import build_model
    return FlatParamSpec.for_model(build_model(MAMBA))


@pytest.fixture(scope="module")
def flat_len(flat_spec):
    return flat_spec.padded


def _compile(fn, sharding, *shapes, donate=()):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    text = jax.jit(fn, donate_argnums=donate).lower(*args).compile() \
        .as_text()
    assert "tpu_custom_call" in text
    return text


def test_consensus_mix_compiles(one_chip, flat_len):
    from repro.kernels.consensus_mix import consensus_mix
    N = REPLICAS // CLUSTER
    _compile(lambda z, V, g: consensus_mix(z, V, g, interpret=False),
             one_chip, ((N, CLUSTER, flat_len), jnp.float32),
             ((N, CLUSTER, CLUSTER), jnp.float32), ((N,), jnp.int32))


def test_fused_sgd_compiles(one_chip, flat_len):
    from repro.kernels.fused_sgd import fused_sgd
    _compile(lambda w, g, e: fused_sgd(w, g, e, weight_decay=1e-4,
                                       interpret=False),
             one_chip, ((flat_len,), jnp.float32),
             ((flat_len,), jnp.float32), ((), jnp.float32))


def test_fused_consensus_sgd_compiles(one_chip, flat_spec):
    """The whole 4-replica carrier: fits 16 GB only because the kernel
    updates the (donated) parameter buffer in place."""
    from repro.core.distributed import LANE
    from repro.kernels.fused_consensus_sgd import fused_consensus_sgd
    N = REPLICAS // CLUSTER
    carrier = (N, CLUSTER, flat_spec.rows, LANE)
    _compile(lambda w, g, W, e: fused_consensus_sgd(w, g, W, e,
                                                    interpret=False),
             one_chip, (carrier, jnp.float32), (carrier, jnp.float32),
             ((N, CLUSTER, CLUSTER), jnp.float32), ((), jnp.float32),
             donate=(0,))


def test_ssd_scan_compiles(one_chip):
    from repro.kernels.ssd_scan import ssd_scan
    BH, T = MAMBA.ssm_num_heads, 512          # one sequence, 32 heads
    P, S = MAMBA.ssm_head_dim, MAMBA.ssm_state_dim
    _compile(lambda x, dt, la, B, C: ssd_scan(
        x, dt, la, B, C, chunk=MAMBA.ssm_chunk, interpret=False),
        one_chip, ((BH, T, P), jnp.float32), ((BH, T), jnp.float32),
        ((BH, T), jnp.float32), ((BH, T, S), jnp.float32),
        ((BH, T, S), jnp.float32))


PAGED_ATTN_CASES = {
    # qwen1.5-0.5b: head dim 64, lane-padded pool rows (page-grid walk)
    "qwen": dict(B=4, K=QWEN.num_kv_heads,
                 G=QWEN.num_heads // QWEN.num_kv_heads, hd=64, ps=16,
                 pages_per_slot=16, window=0),
    # the code cell (starcoder2-3b): 16 slots of 4,096 tokens, 4k window
    "code_cell": dict(B=16, K=STARCODER.num_kv_heads,
                      G=STARCODER.num_heads // STARCODER.num_kv_heads,
                      hd=STARCODER.head_dim, ps=16, pages_per_slot=256,
                      window=STARCODER.sliding_window),
}


@pytest.mark.parametrize("case", sorted(PAGED_ATTN_CASES))
def test_paged_attn_compiles(one_chip, case):
    from repro.kernels.paged_attn import paged_decode
    c = PAGED_ATTN_CASES[case]
    B, K, G, hd, ps = c["B"], c["K"], c["G"], c["hd"], c["ps"]
    num_pages = B * c["pages_per_slot"] + 1
    pool = (num_pages, ps, K, hd)
    text = _compile(lambda q, k, v, pm, pos: paged_decode(
        q, k, v, pm, pos, window=c["window"], interpret=False), one_chip,
        ((B, K, G, hd), jnp.float32), (pool, jnp.float32),
        (pool, jnp.float32), ((B, c["pages_per_slot"]), jnp.int32),
        ((B,), jnp.int32))
    if hd % 128 == 0:
        # the pools reach the kernel as they are: no copy or relayout
        shape = "f32[" + ",".join(map(str, pool)) + "]"
        made = [ln for ln in text.splitlines()
                if f"= {shape}" in ln and " parameter(" not in ln]
        assert not made, made
