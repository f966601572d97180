"""Per-kernel allclose vs the pure-jnp oracles, swept over shapes and
dtypes (interpret mode on CPU — the kernel body itself executes)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.topology import metropolis_weights, ring_adjacency, \
    geometric_adjacency
from repro.kernels import ops, ref


def _V(N, s, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        np.stack([metropolis_weights(geometric_adjacency(s, 0.9, rng))
                  for _ in range(N)]), jnp.float32)


# ---------------------------------------------------------------------------
# consensus_mix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("N,s,M", [(1, 2, 8), (3, 5, 100), (4, 8, 700),
                                   (2, 5, 513), (25, 5, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_consensus_mix_shapes(N, s, M, dtype):
    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.normal(size=(N, s, M)), dtype)
    V = _V(N, s)
    gamma = jnp.asarray(rng.integers(0, 6, size=(N,)), jnp.int32)
    out = ops.consensus_mix(z, V, gamma)
    expect = ref.consensus_mix_ref(z, V, gamma)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=tol)


@given(gamma=st.integers(0, 8), blk=st.sampled_from([64, 128, 512]),
       seed=st.integers(0, 20))
@settings(max_examples=12, deadline=None)
def test_consensus_mix_block_size_invariance(gamma, blk, seed):
    rng = np.random.default_rng(seed)
    N, s, M = 2, 5, 200
    z = jnp.asarray(rng.normal(size=(N, s, M)), jnp.float32)
    V = _V(N, s, seed)
    g = jnp.full((N,), gamma, jnp.int32)
    out = ops.consensus_mix(z, V, g, blk_m=blk)
    expect = ref.consensus_mix_ref(z, V, g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-4)


def test_consensus_mix_preserves_mean():
    rng = np.random.default_rng(1)
    N, s, M = 3, 5, 96
    z = jnp.asarray(rng.normal(size=(N, s, M)), jnp.float32)
    V = _V(N, s, 1)
    out = ops.consensus_mix(z, V, jnp.full((N,), 7, jnp.int32))
    np.testing.assert_allclose(np.asarray(out.mean(1)),
                               np.asarray(z.mean(1)), atol=1e-4)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("BH,T,P,S,chunk", [
    (1, 64, 16, 16, 16), (2, 256, 64, 128, 128), (3, 512, 64, 128, 256),
    (2, 130, 32, 64, 64),   # ragged T -> padding path in ops
])
def test_ssd_scan_shapes(BH, T, P, S, chunk):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(BH, T, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, size=(BH, T)), jnp.float32)
    loga = -dt * jnp.asarray(rng.uniform(0.5, 2.0, size=(BH, 1)),
                             jnp.float32)
    B = jnp.asarray(rng.normal(size=(BH, T, S)), jnp.float32) * 0.3
    C = jnp.asarray(rng.normal(size=(BH, T, S)), jnp.float32) * 0.3
    yk, hk = ops.ssd_scan(x, dt, loga, B, C, chunk=chunk)
    yr, hr = ref.ssd_scan_ref(x, dt, loga, B, C)
    scale = float(jnp.abs(yr).max()) + 1e-6
    assert float(jnp.abs(yk - yr).max()) / scale < 1e-4
    if T % chunk == 0:   # padded case: final state includes padding steps
        np.testing.assert_allclose(np.asarray(hk), np.asarray(hr),
                                   rtol=1e-4, atol=1e-4)


def test_ssd_scan_state_carry_across_chunks():
    """Splitting T into chunks must equal one long scan (state carry)."""
    rng = np.random.default_rng(2)
    BH, T, P, S = 2, 256, 32, 64
    x = jnp.asarray(rng.normal(size=(BH, T, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.1, size=(BH, T)), jnp.float32)
    loga = -dt
    B = jnp.asarray(rng.normal(size=(BH, T, S)), jnp.float32) * 0.3
    C = jnp.asarray(rng.normal(size=(BH, T, S)), jnp.float32) * 0.3
    y64, _ = ops.ssd_scan(x, dt, loga, B, C, chunk=64)
    y256, _ = ops.ssd_scan(x, dt, loga, B, C, chunk=256)
    np.testing.assert_allclose(np.asarray(y64), np.asarray(y256),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# fused_sgd
# ---------------------------------------------------------------------------

# tiny leaves (n < 128) and odd sizes straddling the lane width pin the
# block-size logic: blocks must stay lane multiples, pad must trim back
@pytest.mark.parametrize("shape", [(8,), (127,), (129,), (1000, 37),
                                   (3, 5, 7, 11)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("wd", [0.0, 0.1])
def test_fused_sgd(shape, dtype, wd):
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=shape), dtype)
    g = jnp.asarray(rng.normal(size=shape), dtype)
    out = ops.fused_sgd(w, g, 0.01, weight_decay=wd)
    expect = ref.fused_sgd_ref(w, g, jnp.asarray(0.01), weight_decay=wd)
    tol = 1e-6 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=tol)


def test_fused_sgd_block_is_lane_aligned():
    from repro.kernels.fused_sgd import LANE
    # n just under/over the lane width must still produce lane-multiple
    # blocks (the old min(blk, max(n, 8)) could hand Mosaic blk=37)
    for n in (8, 127, 128, 129, 1000 * 37):
        blk = max(LANE, min(65_536, -(-n // LANE) * LANE))
        assert blk % LANE == 0


# ---------------------------------------------------------------------------
# fused_consensus_sgd: last-microstep SGD + W-mixing in one pass
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("N,s,rows", [(2, 4, 1), (4, 2, 37), (1, 8, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("wd", [0.0, 0.1])
def test_fused_consensus_sgd(N, s, rows, dtype, wd):
    rng = np.random.default_rng(3)
    shape = (N, s, rows, 128)
    w = jnp.asarray(rng.normal(size=shape), dtype)
    g = jnp.asarray(rng.normal(size=shape), dtype)
    V = _V(N, s)
    W = jnp.asarray(np.stack([np.linalg.matrix_power(
        np.asarray(V[c], np.float64), 2) for c in range(N)]), jnp.float32)
    out = ops.fused_consensus_sgd(w, g, W, 0.01, weight_decay=wd)
    expect = ref.fused_consensus_sgd_ref(w, g, W, jnp.asarray(0.01),
                                         weight_decay=wd)
    assert out.shape == shape and out.dtype == dtype
    # bf16: the ref rounds to bf16 between the SGD update and the mix,
    # the kernel keeps f32 throughout — up to ~2 bf16 ulp apart, so the
    # bound must scale with magnitude (rtol), not be purely absolute
    tol = 1e-6 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


def test_fused_consensus_sgd_matches_jitted_two_pass():
    """vs the jitted unfused two-pass graph (SGD then mix) — the jit-to-
    jit comparison the fused-interval step's bitwise contract rests on.
    37 rows in blocks of 16 also cover the ragged last block."""
    from repro.core.mixing import mix_blocks
    from repro.kernels.fused_consensus_sgd import fused_consensus_sgd
    N, s, rows = 2, 4, 37
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.normal(size=(N, s, rows, 128)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(N, s, rows, 128)), jnp.float32)
    V = _V(N, s)
    W = jnp.asarray(np.stack([np.linalg.matrix_power(
        np.asarray(V[c], np.float64), 3) for c in range(N)]), jnp.float32)

    @jax.jit
    def two_pass(w, g, W):
        wp = w - jnp.float32(0.01) * g
        return mix_blocks(W, wp)

    fused = fused_consensus_sgd(w, g, W, jnp.float32(0.01), blk_rows=16)
    assert np.array_equal(np.asarray(fused), np.asarray(two_pass(w, g, W)))


def test_trainer_with_kernel_matches_without():
    """The sim engine with use_kernel=True must train identically."""
    import dataclasses
    from repro.configs import TopologyConfig, TTHFConfig
    from repro.core import TTHFTrainer
    from repro.data import fashion_synth, partition_noniid_labels
    from repro.models import make_sim_model

    x, y = fashion_synth(num_points=800, seed=0)
    data = partition_noniid_labels(x, y, num_devices=10)
    topo = TopologyConfig(num_devices=10, num_clusters=2, graph="ring")
    model = make_sim_model("svm", 784, 10)
    algo = TTHFConfig(tau=5, consensus_every=2, gamma_d2d=2,
                      constant_lr=0.002)
    runs = []
    for uk in (False, True):
        tr = TTHFTrainer(model, data, topo, algo, batch_size=8,
                         use_kernel=uk)
        _, hist = tr.run(steps=10, eval_every=5, seed=0)
        runs.append(hist.global_loss)
    np.testing.assert_allclose(runs[0], runs[1], rtol=1e-4)
