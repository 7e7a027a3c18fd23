"""Per-kernel shape/dtype sweeps vs pure-jnp oracles (on CPU the Pallas
kernels run interpreted; tests/test_tpu_compile.py compiles them for a
TPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.reservoir.ops import reservoir_topm
from repro.kernels.gather.ops import cache_gather
from repro.kernels.segment_agg.ops import neighbor_mean
from repro.kernels.flash_attention.ops import flash_attention

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# reservoir
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("R,N,m", [(8, 16, 4), (13, 37, 5), (32, 200, 15),
                                   (8, 128, 25), (1, 5, 3)])
def test_reservoir_matches_ref(R, N, m):
    w = jnp.asarray(RNG.uniform(0.5, 4.0, (R, N)), jnp.float32)
    u = jnp.asarray(RNG.random((R, N)), jnp.float32)
    mask = jnp.asarray(RNG.random((R, N)) < 0.8)
    i1, k1 = reservoir_topm(w, u, mask, m, use_pallas=True)
    i2, k2 = reservoir_topm(w, u, mask, m, use_pallas=False)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(k1), np.asarray(k2), rtol=1e-6)


def test_reservoir_top_by_key():
    """Kernel selection == numpy top-m of the same ES keys."""
    R, N, m = 6, 50, 7
    w = RNG.uniform(0.5, 4.0, (R, N)).astype(np.float32)
    u = RNG.random((R, N)).astype(np.float32)
    mask = RNG.random((R, N)) < 0.7
    idx, _ = reservoir_topm(jnp.asarray(w), jnp.asarray(u), jnp.asarray(mask), m)
    keys = np.log(np.maximum(u, 1e-30)) / np.maximum(w, 1e-9)
    keys[~mask] = -np.inf
    for r in range(R):
        nv = int(mask[r].sum())
        want = set(np.argsort(-keys[r], kind="stable")[:min(m, nv)].tolist())
        got = np.asarray(idx)[r]
        got = set(got[got < N][:min(m, nv)].tolist())
        assert want == got


def test_reservoir_distribution_matches_sequential():
    """Kernel sampling distribution == Algo. 2 (statistical)."""
    from repro.core.sampling import reservoir_sample_ref
    N, m, trials = 8, 2, 3000
    w = np.array([4, 4, 1, 1, 1, 1, 1, 1], np.float32)
    counts_k = np.zeros(N)
    counts_r = np.zeros(N)
    rng = np.random.default_rng(7)
    us = rng.random((trials, N)).astype(np.float32)
    idx, _ = reservoir_topm(jnp.tile(w, (trials, 1)), jnp.asarray(us),
                            jnp.ones((trials, N), bool), m)
    for row in np.asarray(idx):
        counts_k[row[row < N]] += 1
    rng2 = np.random.default_rng(8)
    for _ in range(trials):
        out = reservoir_sample_ref(np.arange(N), w, m, rng2)
        counts_r[out] += 1
    np.testing.assert_allclose(counts_k / counts_k.sum(),
                               counts_r / counts_r.sum(), atol=0.03)


# ---------------------------------------------------------------------------
# gather
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,C,F", [(8, 16, 256), (37, 64, 512),
                                   (100, 200, 1024), (5, 8, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_matches_ref(n, C, F, dtype):
    cache = jnp.asarray(RNG.normal(0, 1, (C, F))).astype(dtype)
    slots = jnp.asarray(RNG.integers(-1, C, n), jnp.int32)
    o1, m1 = cache_gather(slots, cache, use_pallas=True)
    o2, m2 = cache_gather(slots, cache, use_pallas=False)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32))
    assert np.array_equal(np.asarray(m1), np.asarray(m2))


def test_gather_miss_semantics():
    cache = jnp.ones((8, 128), jnp.float32)
    slots = jnp.asarray([0, -1, 3, -1], jnp.int32)
    out, miss = cache_gather(slots, cache)
    assert np.array_equal(np.asarray(miss), [0, 1, 0, 1])
    assert np.asarray(out)[1].sum() == 0            # miss rows zeroed


@pytest.mark.parametrize("n,C,F", [(37, 16, 602), (5, 8, 300), (3, 4, 700),
                                   (100, 32, 602)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_contract_non_multiple_of_block(n, C, F, dtype):
    """Regression: the miss-path shape/dtype contract must hold for batch
    sizes that are not a multiple of the id block AND feature widths that
    are not a multiple of the feature block (reddit F=602, yelp F=300) —
    the kernel path used to assert out on F % block_f."""
    cache = jnp.asarray(RNG.normal(0, 1, (C, F))).astype(dtype)
    slots = jnp.asarray(RNG.integers(-1, C, n), jnp.int32)
    o1, m1 = cache_gather(slots, cache, use_pallas=True)
    o2, m2 = cache_gather(slots, cache, use_pallas=False)
    for o, m in ((o1, m1), (o2, m2)):
        assert o.shape == (n, F) and m.shape == (n,)
        assert o.dtype == cache.dtype               # no silent promotion
        assert m.dtype == jnp.int32
    assert np.array_equal(np.asarray(o1, np.float32),
                          np.asarray(o2, np.float32))
    assert np.array_equal(np.asarray(m1), np.asarray(m2))
    # padded-row misses never leak into the sliced result
    assert np.array_equal(np.asarray(m1), np.asarray(slots) < 0)


# ---------------------------------------------------------------------------
# segment aggregation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Nd,Ns,F,fan", [(16, 32, 256, 5), (7, 9, 256, 10),
                                         (64, 128, 512, 25), (8, 8, 1024, 3)])
def test_segment_agg_matches_ref(Nd, Ns, F, fan):
    h = jnp.asarray(RNG.normal(0, 1, (Ns, F)), jnp.float32)
    idx = jnp.asarray(RNG.integers(-1, Ns, (Nd, fan)), jnp.int32)
    o1 = neighbor_mean(idx, h, use_pallas=True)
    o2 = neighbor_mean(idx, h, use_pallas=False)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5,
                               rtol=1e-5)


def test_segment_agg_all_padded_row():
    h = jnp.ones((4, 128), jnp.float32)
    idx = jnp.full((2, 5), -1, jnp.int32)
    out = neighbor_mean(idx, h)
    assert np.asarray(out).sum() == 0.0


def test_segment_agg_forwards_interpret_flag(monkeypatch):
    """Regression: the aggregation kernel once ran interpreted on a real
    TPU because the wrapper dropped the caller's flag.  The wrapper now
    decides it from the platform it is lowered for: it hands the kernel
    interpret=False for a TPU and interpret=True for any other backend,
    and only the branch for the lowering platform is lowered.  Spy on
    the kernel entry point and assert both branches are built."""
    import jax
    from repro.kernels.segment_agg import ops as agg_ops
    from repro.kernels.segment_agg.kernel import neighbor_agg_pallas
    seen = []

    def spy(idx, h, *args, interpret, **kw):
        seen.append(interpret)
        return neighbor_agg_pallas(idx, h, *args, interpret=interpret, **kw)

    monkeypatch.setattr(agg_ops, "neighbor_agg_pallas", spy)
    h = jnp.ones((11, 128), jnp.float32)          # distinctive shape: the
    idx = jnp.zeros((3, 2), jnp.int32)            # jit cache must retrace
    lowered = agg_ops.neighbor_mean.lower(idx, h, use_pallas=True)
    assert sorted(seen) == [False, True]
    on_tpu = jax.default_backend() == "tpu"
    assert ("tpu_custom_call" in lowered.as_text()) == on_tpu
    out = agg_ops.neighbor_mean(idx, h, use_pallas=True)
    np.testing.assert_allclose(np.asarray(out), np.ones((3, 128)))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,Dh,H,causal", [(128, 64, 2, True),
                                           (256, 128, 1, True),
                                           (128, 128, 3, False),
                                           (512, 64, 2, True)])
def test_flash_matches_ref_f32(S, Dh, H, causal):
    q = jnp.asarray(RNG.normal(0, 1, (2, S, H, Dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (2, S, H, Dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (2, S, H, Dh)), jnp.float32)
    o1 = flash_attention(q, k, v, causal=causal, use_pallas=True)
    o2 = flash_attention(q, k, v, causal=causal, use_pallas=False)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5,
                               rtol=1e-4)


def test_flash_bf16():
    q = jnp.asarray(RNG.normal(0, 1, (1, 256, 2, 64))).astype(jnp.bfloat16)
    k = jnp.asarray(RNG.normal(0, 1, (1, 256, 2, 64))).astype(jnp.bfloat16)
    v = jnp.asarray(RNG.normal(0, 1, (1, 256, 2, 64))).astype(jnp.bfloat16)
    o1 = flash_attention(q, k, v, use_pallas=True)
    o2 = flash_attention(q, k, v, use_pallas=False)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=3e-2)


def test_flash_matches_model_attention():
    """Kernel == the XLA-native attention used by the LM stack."""
    from repro.models import layers as L
    from repro.configs import get_config
    cfg = get_config("minitron-8b", smoke=True).replace(attn_chunk=0,
                                                        use_rope=False)
    B, S, H, Dh = 2, 128, cfg.num_heads, cfg.head_dim
    q = jnp.asarray(RNG.normal(0, 1, (B, S, H, Dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (B, S, H, Dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (B, S, H, Dh)), jnp.float32)
    o_kernel = flash_attention(q, k, v, causal=True)
    o_model = L._attend(q, k, v,
                        lambda qi, ki: qi[:, None] >= ki[None, :], Dh ** -0.5)
    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_model),
                               atol=2e-5, rtol=1e-4)
