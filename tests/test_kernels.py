"""Per-kernel shape/dtype sweeps against pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.aggregate.kernel import aggregate_kernel
from repro.kernels.aggregate.ops import aggregate_trees
from repro.kernels.aggregate.ref import aggregate_ref
from repro.kernels.flash_attention.ops import flash_attention_padded
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.similarity.kernel import pairwise_kernel
from repro.kernels.similarity.ops import (
    pairwise_distances_chunked,
    pairwise_distances_device,
    pairwise_distances_streamed,
)
from repro.kernels.similarity.ref import gram_ref, l1_ref
from repro.core.clustering.similarity import pairwise_distances as np_pairwise

RNG = np.random.default_rng(0)


# --------------------------------------------------------------------------
# similarity
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n,d", [(8, 16), (33, 70), (64, 128), (100, 257)])
@pytest.mark.parametrize("op", ["gram", "l1"])
def test_pairwise_kernel_shapes(n, d, op):
    G = jnp.asarray(RNG.normal(size=(n, d)), jnp.float32)
    got = pairwise_kernel(G, op=op, block_n=16, block_d=32, interpret=True)
    ref = gram_ref(G) if op == "gram" else l1_ref(G)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-3)


@pytest.mark.parametrize("measure", ["arccos", "l2", "l1"])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_pairwise_distances_vs_numpy_reference(measure, dtype):
    G = RNG.normal(size=(21, 45)).astype(dtype)
    dev = np.asarray(
        pairwise_distances_device(G, measure, block_n=8, block_d=16, interpret=True)
    )
    ref = np_pairwise(G, measure)
    np.testing.assert_allclose(dev, ref, atol=1e-4)


def test_pairwise_distance_zero_rows():
    G = np.zeros((5, 12), np.float32)
    G[2] = RNG.normal(size=12)
    dev = np.asarray(pairwise_distances_device(G, "arccos", interpret=True, block_n=8))
    assert dev[0, 1] == 0.0
    np.testing.assert_allclose(dev[0, 2], np.pi / 2, atol=1e-6)


@pytest.mark.parametrize("measure", ["arccos", "l2", "l1"])
def test_pairwise_zero_rows_parity_with_numpy_reference(measure):
    """Cold-start clients (never sampled) carry all-zero representative
    gradients: the device path must match the numpy reference exactly on
    mixed zero/non-zero G, including arccos's zero-vs-zero -> 0 and
    zero-vs-nonzero -> pi/2 conventions."""
    G = RNG.normal(size=(9, 24)).astype(np.float32)
    G[[0, 3, 7]] = 0.0  # never-sampled clients
    dev = np.asarray(
        pairwise_distances_device(G, measure, block_n=8, block_d=16, interpret=True)
    )
    ref = np_pairwise(G, measure)
    np.testing.assert_allclose(dev, ref, atol=1e-5)
    if measure == "arccos":
        assert dev[0, 3] == 0.0 and dev[3, 7] == 0.0
        np.testing.assert_allclose(dev[0, 1], np.pi / 2, atol=1e-6)
        np.testing.assert_allclose(dev[7, 2], np.pi / 2, atol=1e-6)


@pytest.mark.parametrize("measure", ["arccos", "l2", "l1"])
@pytest.mark.parametrize(
    "n,d,d_chunk",
    [
        (21, 45, 16),   # non-multiple n and d, ragged final chunk
        (33, 130, 32),  # 5 chunks, none aligned to the block size
        (16, 64, 64),   # single chunk == one-shot degenerate case
        (9, 200, 64),   # d >> n, the model-sized-d regime in miniature
    ],
)
def test_streamed_matches_one_shot_and_numpy(measure, n, d, d_chunk):
    """The fused streamed kernel, the legacy chunked loop, the one-shot
    kernel and the f64 numpy reference must all agree across all three
    measures (Gram and L1 are both exact sums over coordinate chunks)."""
    G = RNG.normal(size=(n, d)).astype(np.float32)
    st = np.asarray(
        pairwise_distances_streamed(
            G, measure, block_n=8, block_d=16, d_chunk=d_chunk, interpret=True
        )
    )
    ch = np.asarray(
        pairwise_distances_chunked(
            G, measure, block_n=8, block_d=16, d_chunk=d_chunk, interpret=True
        )
    )
    one = np.asarray(
        pairwise_distances_device(G, measure, block_n=8, block_d=16, interpret=True)
    )
    np.testing.assert_allclose(st, one, atol=1e-4)
    np.testing.assert_allclose(st, ch, atol=1e-4)
    np.testing.assert_allclose(st, np_pairwise(G, measure), atol=1e-4)
    assert (np.diag(st) == 0).all()
    np.testing.assert_allclose(st, st.T)


@pytest.mark.parametrize("measure", ["arccos", "l1"])
def test_chunked_never_sees_full_width_block(measure, monkeypatch):
    """The chunked parity path must hand the kernel (n, <= d_chunk) slabs
    only — the padded (n, d) block of the one-shot path is never
    materialized."""
    from repro.kernels.similarity import ops

    widths = []
    real = ops.pairwise_kernel

    def spy(G, **kw):
        widths.append(int(G.shape[1]))
        return real(G, **kw)

    monkeypatch.setattr(ops, "pairwise_kernel", spy)
    G = RNG.normal(size=(12, 100)).astype(np.float32)
    out = np.asarray(
        pairwise_distances_chunked(
            G, measure, block_n=8, block_d=16, d_chunk=32, interpret=True
        )
    )
    assert widths == [32, 32, 32, 4]  # chunked cover of d=100, ragged tail
    np.testing.assert_allclose(out, np_pairwise(G, measure), atol=1e-4)


@pytest.mark.parametrize("measure", ["arccos", "l1"])
def test_fused_streamed_no_pad_no_chunk_loop(measure, monkeypatch):
    """The fused path is ONE kernel launch on the unpadded G: no padded
    (n, d) block is built by the pipeline (the fused kernel receives G at
    its exact ragged shape — interpret mode's internal block emulation is
    the emulator's business, a compiled run feeds HBM directly) and no host
    d-chunk loop runs (the padded one-shot kernel is never called, the
    fused kernel exactly once), on a shape ragged in both n and d."""
    from repro.kernels.similarity import ops

    calls = []
    real_fused = ops.pairwise_kernel_fused

    def fused_spy(G, **kw):
        calls.append(tuple(G.shape))
        return real_fused(G, **kw)

    def one_shot_trap(G, **kw):
        raise AssertionError("fused path fell back to the padded one-shot kernel")

    monkeypatch.setattr(ops, "pairwise_kernel_fused", fused_spy)
    monkeypatch.setattr(ops, "pairwise_kernel", one_shot_trap)
    G = RNG.normal(size=(13, 101)).astype(np.float32)  # ragged n AND d
    out = np.asarray(
        pairwise_distances_streamed(
            G, measure, block_n=8, block_d=16, d_chunk=32, interpret=True
        )
    )
    assert calls == [(13, 101)]  # exactly one launch, G handed over unpadded
    np.testing.assert_allclose(out, np_pairwise(G, measure), atol=1e-4)


def test_streamed_zero_rows_conventions():
    """Cold-start (all-zero) rows keep the arccos conventions under
    chunked accumulation: zero-vs-zero -> 0, zero-vs-nonzero -> pi/2."""
    G = RNG.normal(size=(7, 40)).astype(np.float32)
    G[[1, 4]] = 0.0
    st = np.asarray(
        pairwise_distances_streamed(
            G, "arccos", block_n=8, block_d=16, d_chunk=16, interpret=True
        )
    )
    assert st[1, 4] == 0.0
    np.testing.assert_allclose(st[1, 0], np.pi / 2, atol=1e-6)


def test_streamed_backend_resolves():
    from repro.kernels.similarity.ops import resolve_distance_backend

    fn = resolve_distance_backend("streamed")
    G = RNG.normal(size=(10, 30)).astype(np.float32)
    np.testing.assert_allclose(fn(G, "l2"), np_pairwise(G, "l2"), atol=1e-4)


def test_pallas_backend_requires_tpu():
    """The compiled kernel uses pltpu.VMEM scratch — requesting it off-TPU
    must be a clear error, not a mosaic traceback at first distance call."""
    import jax

    from repro.kernels.similarity.ops import resolve_distance_backend

    if jax.default_backend() == "tpu":
        pytest.skip("compiled pallas is legitimate on TPU")
    with pytest.raises(RuntimeError, match="requires a TPU"):
        resolve_distance_backend("pallas")


def test_interpret_mode_follows_the_platform(monkeypatch):
    """CPU interprets, TPU compiles, and any other platform is an error
    rather than a silent interpreter run."""
    from repro.kernels import interpret_mode

    assert interpret_mode() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        interpret_mode()
    from repro.kernels.similarity.ops import resolve_distance_backend

    with pytest.raises(RuntimeError, match="'gpu'"):
        resolve_distance_backend("auto")


# --------------------------------------------------------------------------
# aggregate
# --------------------------------------------------------------------------
@pytest.mark.parametrize("k,p", [(1, 64), (7, 1000), (32, 4096), (11, 12345)])
def test_aggregate_kernel_sweep(k, p):
    U = jnp.asarray(RNG.normal(size=(k, p)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(k,)), jnp.float32)
    got = aggregate_kernel(U, w, block_p=512, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(aggregate_ref(U, w)), rtol=2e-5, atol=2e-5
    )


def test_aggregate_trees_matches_tree_arithmetic():
    trees = [
        {"a": jnp.asarray(RNG.normal(size=(4, 5)), jnp.float32), "b": jnp.asarray(RNG.normal(size=(7,)), jnp.float32)}
        for _ in range(3)
    ]
    w = np.array([0.2, 0.3, 0.5])
    got = aggregate_trees(trees, w, interpret=True)
    from repro.fl.aggregation import weighted_tree_sum

    ref = weighted_tree_sum(trees, w)
    for kk in ("a", "b"):
        np.testing.assert_allclose(np.asarray(got[kk]), np.asarray(ref[kk]), atol=1e-5)


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------
@pytest.mark.parametrize(
    "b,s,h,kv,hd", [(1, 32, 4, 4, 16), (2, 64, 8, 2, 32), (1, 48, 6, 1, 64), (2, 40, 4, 2, 8)]
)
def test_flash_attention_gqa_sweep(b, s, h, kv, hd):
    q = jnp.asarray(RNG.normal(size=(b, s, h, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, s, kv, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, s, kv, hd)), jnp.float32)
    got = flash_attention_padded(q, k, v, block_q=16, block_k=16, interpret=True)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_flash_attention_bf16():
    q = jnp.asarray(RNG.normal(size=(1, 32, 4, 16)), jnp.bfloat16)
    k = jnp.asarray(RNG.normal(size=(1, 32, 2, 16)), jnp.bfloat16)
    v = jnp.asarray(RNG.normal(size=(1, 32, 2, 16)), jnp.bfloat16)
    got = flash_attention_padded(q, k, v, block_q=16, block_k=16, interpret=True)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


def test_flash_attention_matches_model_layer():
    """Kernel output == the model's attend() (same masking semantics)."""
    from repro.configs import get_config
    from repro.models.layers.attention import attend, causal_mask

    cfg = get_config("qwen2-1.5b", reduced=True)
    b, s, h, kv, hd = 2, 32, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = jnp.asarray(RNG.normal(size=(b, s, h, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, s, kv, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, s, kv, hd)), jnp.float32)
    model_out = attend(cfg, q, k, v, causal_mask(s, s, 0)).reshape(b, s, h, hd)
    kern_out = flash_attention_padded(q, k, v, block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(kern_out), np.asarray(model_out), atol=2e-5)
