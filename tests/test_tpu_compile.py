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
import json
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.configs.base import ModelConfig

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


# --------------------------------------------- serving programs at size

BENCH_CONFIGS = Path(__file__).resolve().parents[1] / "bench" / "configs"
# the serving cells: configuration, slots, max_total (page 16, chunk 256)
SERVE_CELLS = {"code": ("starcoder2-3b-18l", 16, 4096),
               "chat": ("mamba2-370m", 32, 768)}


def _cell_model(name):
    from repro.models import build_model
    model = json.loads((BENCH_CONFIGS / f"{name}.json").read_text())["model"]
    return build_model(ModelConfig(**model))


def _serve_program(model, program, slots, max_total, sharding):
    """(jitted fn, abstract non-param args) of the paged scheduler's
    ``serve_decode`` or ``serve_prefill_chunk`` at a cell's shapes,
    donating the cache (and the chunk's logits) as the scheduler does."""
    from repro.serving import pages_per_slot
    ps, C = 16, 256
    P = pages_per_slot(max_total, ps)
    cache = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        model.abstract_paged_cache(slots, slots * P + 1, ps, jnp.float32))
    arg = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=sharding)  # noqa: E731
    if program == "decode":
        def fn(p, t, c, s, pm, lv):
            return model.decode_step_paged(p, t, c, s, pm, lv,
                                           dtype=jnp.float32,
                                           use_kernel=True)
        return jax.jit(fn, donate_argnums=(2,)), (
            arg(jnp.int32, slots, 1), cache, arg(jnp.int32, slots),
            arg(jnp.int32, slots, P), arg(bool, slots))

    def fn(p, c, lg, tokens, start, valid, row, slot):
        c1, lg1 = model.prefill_chunk(p, c, tokens, start, valid, row, slot,
                                      dtype=jnp.float32)
        return c1, jax.lax.dynamic_update_slice(lg, lg1, (slot, 0, 0))
    return jax.jit(fn, donate_argnums=(1, 2)), (
        cache, arg(jnp.float32, slots, 1, model.cfg.padded_vocab),
        arg(jnp.int32, 1, C), arg(jnp.int32), arg(jnp.int32),
        arg(jnp.int32, P), arg(jnp.int32))


@pytest.fixture
def mosaic(monkeypatch):
    """Lower the serving programs with the Mosaic paged-decode kernel, as
    on the chip: off-TPU the kernel defaults to interpret mode, whose
    loop converts the whole f32 pool it reads to bf16."""
    from repro.kernels import runtime
    monkeypatch.setattr(runtime, "default_interpret", lambda: False)


@pytest.mark.parametrize("program", ["decode", "chunk"])
@pytest.mark.parametrize("cell", sorted(SERVE_CELLS))
def test_serving_operands_take_the_weight_converts_out(one_chip, mosaic,
                                                       cell, program):
    """With the stacked matmul weights handed in as bf16 (the serving
    operand tree), no program converts a weight stack on each call, and
    the temporary that held the converted stacks goes."""
    from repro.serving import engine
    name, slots, max_total = SERVE_CELLS[cell]
    model = _cell_model(name)
    f32 = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        model.abstract_params()[0])
    ops = engine.map_operands(
        model.cfg, lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                                  sharding=one_chip), f32)
    stacks = [a.shape for a, b in zip(jax.tree.leaves(ops),
                                      jax.tree.leaves(f32)) if a is not b]
    copy_bytes = sum(2 * int(np.prod(s)) for s in stacks)
    # a convert producing a whole stack, or one layer of it
    shapes = {s[i:] for s in stacks for i in (0, 1)} | \
        {(1,) + s[1:] for s in stacks}
    pat = re.compile(r"= \w+\[([\d,]+)\][^ ]* convert\(")

    def converted(text):
        return [ln.strip()[:120] for ln in text.splitlines()
                if (m := pat.search(ln))
                and tuple(map(int, m.group(1).split(","))) in shapes]

    fn, args = _serve_program(model, program, slots, max_total, one_chip)
    temp = {}
    for side, params in (("f32", f32), ("ops", ops)):
        exe = fn.lower(params, *args).compile()
        temp[side] = exe.memory_analysis().temp_size_in_bytes
        found = converted(exe.as_text())
        assert bool(found) == (side == "f32"), (side, found)
    # the held copy replaces the per-call temporary: the decode programs
    # shed at least its size; the chunk programs 95% of it (the compiler
    # places a few other buffers differently)
    assert temp["f32"] - temp["ops"] >= 0.95 * copy_bytes, \
        (temp, copy_bytes)


# temp_size_in_bytes of each program before the cache was carried
# through the layer loop (undonated), compiled as here: the code decode
# with the Mosaic kernel
UNCARRIED_TEMP = {("code", "decode"): 67_544_576,
                  ("code", "chunk"): 202_553_856,
                  ("chat", "decode"): 67_528_704,
                  ("chat", "chunk"): 1_813_577_728}
_INSTR = re.compile(r"\s+(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                    r"([\w\-]+)\(")


def _outside_fusions(text):
    """``(name, opcode, dtype, dims)`` of each array-valued instruction
    of an HLO module that is not in a fusion's body."""
    fused = set(re.findall(r" fusion\(.*calls=%([\w.\-]+)", text))
    out, comp = [], None
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", ln)
        if head:
            comp = head.group(1)
        elif comp not in fused and (m := _INSTR.match(ln)):
            name, dt, dims, op = m.groups()
            dims = tuple(int(d) for d in dims.split(",") if d)
            out.append((name, op, dt, dims))
    return out


@pytest.mark.parametrize("program", ["decode", "chunk"])
@pytest.mark.parametrize("cell", sorted(SERVE_CELLS))
def test_serving_cache_stays_in_place(one_chip, mosaic, cell, program):
    """The scheduler's programs, compiled with the cache donated, update
    it in place: no instruction outside a fusion's body moves a whole
    layer's page pool or state, or a whole stack (a copy, slice or
    convert), the cache's bytes are aliased to the output, and the
    temporaries are no larger than before the cache was carried. (A
    layer's slab under 1 MiB, chat's conv contexts at 0.79 MB, may be
    sliced out whole.)"""
    from repro.serving import engine
    name, slots, max_total = SERVE_CELLS[cell]
    model = _cell_model(name)
    ops = engine.map_operands(
        model.cfg, lambda a: a.update(dtype=jnp.bfloat16),
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                    sharding=one_chip),
                     model.abstract_params()[0]))
    fn, args = _serve_program(model, program, slots, max_total, one_chip)
    exe = fn.lower(ops, *args).compile()
    text = exe.as_text()
    # the code decode reads the stacked pools through the Mosaic kernel
    kernel = (cell, program) == ("code", "decode")
    assert ("tpu_custom_call" in text) == kernel
    cache = args[1] if program == "decode" else args[0]
    leaves = jax.tree.leaves(cache)
    # element counts of a layer's slab and of a stack, in any shape
    slab = {int(np.prod(a.shape[1:])) for a in leaves
            if np.prod(a.shape[1:]) * a.dtype.itemsize >= 1 << 20}
    stack = {int(np.prod(a.shape)) for a in leaves}
    moved = []
    for name_, op, dt, dims in _outside_fusions(text):
        n = int(np.prod(dims))
        if op in ("parameter", "get-tuple-element", "bitcast"):
            continue
        if n in slab or n in stack and (
                op in ("copy", "copy-done", "dynamic-slice", "convert")
                or any(w in name_ for w in ("copy", "dynamic-slice",
                                            "convert"))):
            moved.append(f"{name_} = {dt}{list(dims)} {op}")
    assert not moved, moved
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves), mem
    assert mem.temp_size_in_bytes <= UNCARRIED_TEMP[cell, program], mem


def test_doc_chat_operands_are_its_params(monkeypatch):
    """granite-4.0-h-small's layers are unrolled per-layer trees, not a
    scanned stack: the operand tree holds no copy, even on a TPU."""
    from repro.serving import engine
    monkeypatch.setattr(engine, "_on_tpu", lambda p: True)
    model = _cell_model("granite-4.0-h-small-10l")
    params = model.abstract_params()[0]
    ops = engine.serve_operands(model.cfg, params)
    assert all(o is p for o, p in zip(jax.tree.leaves(ops),
                                      jax.tree.leaves(params)))
