"""Sketch stage: kernel/reference parity, registry, distance preservation.

Three layers of contract:

* the Pallas SRP kernel is the *same function* as the numpy host
  reference (shared counter-based hash, shared blockwise accumulation
  order), so parity is to f32 tolerance on ragged shapes and any block
  size;
* the ``SKETCHERS`` registry behaves like every other repro registry
  (guarded override, precise unknown-name errors), ``"identity"`` is the
  exact legacy path (same object back), and ``resolve_sketcher`` pins the
  spec-facing validation;
* SRP actually *preserves the geometry the planner consumes*: pairwise
  inner products concentrate (JL), distance orderings survive with margin,
  and k-means cluster structure recovered from the sketch agrees with the
  exact clustering (adjusted Rand pin) — this is why a plan rebuilt from
  (n, d') is trustworthy.
"""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.kernels.sketch import SKETCHERS, Sketcher, resolve_sketcher
from repro.kernels.sketch.ops import (
    CountSketcher,
    IdentitySketcher,
    SRPSketcher,
)
from repro.kernels.sketch.ref import (
    sketch_countsketch_reference,
    sketch_srp_reference,
    srp_sign_block,
)

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.kernels.sketch.kernel import srp_sketch_kernel  # noqa: E402


def _rand(n, d, seed=0):
    return np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)


# --------------------------------------------------------------------------
# kernel vs host reference
# --------------------------------------------------------------------------
@pytest.mark.parametrize(
    "n,d,d_prime,block_n,block_d",
    [
        (13, 1037, 64, 8, 256),   # ragged n and d tails
        (32, 512, 16, 16, 512),   # d == one block exactly
        (8, 96, 8, 8, 32),        # several tiny d-blocks
        (128, 300, 32, 128, 128), # n == one block, ragged d
    ],
)
def test_srp_kernel_matches_reference(n, d, d_prime, block_n, block_d):
    X = _rand(n, d, seed=n + d)
    got = np.asarray(
        srp_sketch_kernel(
            jnp.asarray(X), d_prime=d_prime, seed=7,
            block_n=block_n, block_d=block_d, interpret=True,
        )
    )
    want = sketch_srp_reference(X, d_prime, 7, block_d=block_d)
    assert got.shape == (n, d_prime)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_srp_kernel_block_size_invariant():
    """Different tilings accumulate in different orders — same result to f32."""
    X = _rand(17, 700, seed=3)
    outs = [
        np.asarray(
            srp_sketch_kernel(
                jnp.asarray(X), d_prime=24, seed=1,
                block_n=bn, block_d=bd, interpret=True,
            )
        )
        for bn, bd in [(8, 64), (17, 512), (16, 128)]
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, rtol=1e-4, atol=1e-4)


def test_srp_seed_changes_projection():
    X = _rand(6, 128)
    a = sketch_srp_reference(X, 16, 0)
    b = sketch_srp_reference(X, 16, 1)
    assert not np.allclose(a, b)
    # same seed → bitwise identical regeneration
    np.testing.assert_array_equal(a, sketch_srp_reference(X, 16, 0))


def test_srp_sign_block_is_scaled_rademacher():
    S = srp_sign_block(seed=0, k0=0, bd=64, d_prime=32, d_total=64)
    scale = np.float32(1.0 / np.sqrt(32.0))
    assert set(np.unique(S)) == {-scale, scale}
    # rows past d_total are zeroed (the ragged-tail mask)
    S_tail = srp_sign_block(seed=0, k0=0, bd=64, d_prime=32, d_total=40)
    assert np.all(S_tail[40:] == 0.0)
    np.testing.assert_array_equal(S_tail[:40], S[:40])


# --------------------------------------------------------------------------
# sketcher dispatch
# --------------------------------------------------------------------------
def test_identity_sketcher_returns_same_object():
    sk = SKETCHERS.get("identity")(32)
    X = _rand(4, 32)
    assert sk(X) is X
    assert sk.reference(X) is X
    Xd = jnp.asarray(X)
    assert sk(Xd) is Xd
    assert (sk.d_in, sk.d_out) == (32, 32)


def test_identity_rejects_compressing_dim():
    with pytest.raises(ValueError, match="identity"):
        SKETCHERS.get("identity")(32, 8)


def test_countsketch_device_matches_reference():
    X = _rand(9, 257, seed=5)
    sk = CountSketcher(257, 31, seed=2)
    got = np.asarray(sk(jnp.asarray(X)))
    want = sketch_countsketch_reference(X, 31, 2)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(sk.reference(X), want, rtol=0, atol=0)


def test_srp_sketcher_device_matches_reference():
    X = _rand(5, 300, seed=9)
    sk = SRPSketcher(300, 12, seed=4)
    np.testing.assert_allclose(
        np.asarray(sk(jnp.asarray(X))), sk.reference(X), rtol=1e-5, atol=1e-5
    )


# --------------------------------------------------------------------------
# registry + resolution
# --------------------------------------------------------------------------
def test_registry_unknown_name_lists_options():
    with pytest.raises(ValueError, match="identity"):
        SKETCHERS.get("nope")


def test_registry_register_and_override():
    def factory(d_in, d_prime=None, *, seed=0):
        return IdentitySketcher(d_in, d_in, seed)

    SKETCHERS.register("_test_sk", factory)
    try:
        assert SKETCHERS.get("_test_sk") is factory
        with pytest.raises(ValueError, match="already registered"):
            SKETCHERS.register("_test_sk", factory)
        SKETCHERS.register("_test_sk", factory, override=True)
    finally:
        SKETCHERS.unregister("_test_sk")
    assert "_test_sk" not in SKETCHERS


def test_resolve_sketcher_contract():
    assert resolve_sketcher(None, 64) is None
    sk = resolve_sketcher("srp", 64, 8, seed=3)
    assert (sk.d_in, sk.d_out, sk.seed) == (64, 8, 3)
    # fitted instance passes through, after a d_in check
    assert resolve_sketcher(sk, 64) is sk
    with pytest.raises(ValueError, match="d_in"):
        resolve_sketcher(sk, 128)
    # compressing sketchers demand a dimension, and a sane one
    with pytest.raises(ValueError, match="sketch_dim"):
        resolve_sketcher("srp", 64)
    with pytest.raises(ValueError, match="1 <= d_prime"):
        resolve_sketcher("countsketch", 64, 0)
    with pytest.raises(ValueError, match="1 <= d_prime"):
        resolve_sketcher("srp", 64, 65)


def test_sketcher_base_is_abstract():
    sk = Sketcher(4, 4, 0)
    with pytest.raises(NotImplementedError):
        sk(np.zeros((1, 4), np.float32))


# --------------------------------------------------------------------------
# geometry preservation (the planner's actual requirement)
# --------------------------------------------------------------------------
def test_srp_preserves_inner_products_in_expectation():
    """JL concentration: Gram matrix of the sketch ≈ Gram of the input."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(12, 4096)).astype(np.float32)
    Y = sketch_srp_reference(X, 1024, 0)
    g_exact = X @ X.T
    g_sketch = Y @ Y.T
    # JL error concentrates at the ‖x_i‖‖x_j‖ scale (off-diagonal exact
    # inner products of Gaussian rows are themselves ≈ 0, so *relative*
    # error there is meaningless); expected deviation ~ 1/√d' ≈ 0.03
    norms = np.sqrt(np.diag(g_exact))
    scale = np.outer(norms, norms)
    err = np.abs(g_sketch - g_exact) / scale
    assert float(np.median(err)) < 0.1
    assert float(err.max()) < 0.25
    assert float(np.max(np.abs(np.diag(g_sketch) / np.diag(g_exact) - 1))) < 0.2


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_srp_preserves_distance_ordering(seed):
    """Pairs whose exact arccos/L1 distances (the planner's measures)
    differ by a clear margin keep their order in sketch space — the
    property plan quality rests on."""
    from repro.core.clustering import pairwise_distances

    rng = np.random.default_rng(seed)
    n, d, dp = 10, 2048, 512
    # clients drawn around a few shared directions: iid Gaussian rows all
    # sit ≈ √(2d) apart (no orderable margins at all), which is NOT the
    # planner's regime — heterogeneous client groups produce a genuine
    # spread of angular distances by construction
    anchors = rng.normal(size=(3, d)).astype(np.float32)
    X = (
        anchors[rng.integers(0, 3, size=n)]
        + 0.7 * rng.normal(size=(n, d)).astype(np.float32)
    )
    Y = sketch_srp_reference(X, dp, seed=seed % 7)
    iu = np.triu_indices(n, 1)
    for measure, rel_margin, min_agree in (("arccos", 0.25, 0.9), ("l1", 0.25, 0.85)):
        de = pairwise_distances(X, measure)[iu]
        ds = pairwise_distances(Y.astype(np.float64), measure)[iu]
        # only score pairs separated by a clear relative margin in exact
        # space; JL cannot (and the planner does not need to) rank
        # near-ties. L1 has no JL guarantee of its own — it rides the L2
        # concentration for Gaussian-like rows, hence the looser floor.
        order = np.argsort(de)
        de_s, ds_s = de[order], ds[order]
        a, b = np.triu_indices(de_s.size, 1)
        margin = de_s[b] > (1.0 + rel_margin) * de_s[a]
        agree = ds_s[b][margin] > ds_s[a][margin]
        assert margin.sum() > 0, measure
        assert float(agree.mean()) >= min_agree, measure


def _adjusted_rand(a: np.ndarray, b: np.ndarray) -> float:
    """Adjusted Rand index (local helper; no sklearn in the image)."""
    a = np.asarray(a)
    b = np.asarray(b)
    n = a.size
    ua, ia = np.unique(a, return_inverse=True)
    ub, ib = np.unique(b, return_inverse=True)
    C = np.zeros((ua.size, ub.size), np.int64)
    np.add.at(C, (ia, ib), 1)
    comb = lambda x: x * (x - 1) / 2.0
    sum_c = comb(C).sum()
    sum_a = comb(C.sum(1)).sum()
    sum_b = comb(C.sum(0)).sum()
    expected = sum_a * sum_b / comb(n)
    max_index = 0.5 * (sum_a + sum_b)
    if max_index == expected:
        return 1.0
    return float((sum_c - expected) / (max_index - expected))


def test_sketched_clustering_agrees_with_exact():
    """k-means labels from the (n, d') sketch match the exact (n, d) labels
    up to permutation (ARI pin) on separated clusters — the end-to-end
    reason a sketched plan rebuild is sound."""
    from repro.core.clustering.device import kmeans_labels

    rng = np.random.default_rng(1)
    n_per, k, d, dp = 30, 4, 2048, 64
    centers = rng.normal(size=(k, d)).astype(np.float32) * 3.0
    X = np.concatenate(
        [c + rng.normal(size=(n_per, d)).astype(np.float32) for c in centers]
    )
    truth = np.repeat(np.arange(k), n_per)
    Y = sketch_srp_reference(X, dp, 0)
    # seed 38's init permutation covers all 4 planted clusters (one row
    # each), so Lloyd converges to the planted optimum in *both* spaces —
    # this isolates the sketch's effect from k-means init local optima,
    # which split clusters identically with or without sketching
    lab_exact = np.asarray(kmeans_labels(jnp.asarray(X), k, seed=38))
    lab_sketch = np.asarray(kmeans_labels(jnp.asarray(Y), k, seed=38))
    assert _adjusted_rand(lab_exact, lab_sketch) >= 0.8
    assert _adjusted_rand(lab_sketch, truth) >= 0.8


def test_adjusted_rand_helper_sanity():
    a = np.array([0, 0, 1, 1])
    assert _adjusted_rand(a, a) == 1.0
    assert _adjusted_rand(a, np.array([1, 1, 0, 0])) == 1.0  # permutation
    rng = np.random.default_rng(0)
    big = rng.integers(0, 3, size=600)
    assert abs(_adjusted_rand(big, rng.permutation(big))) < 0.1  # ≈ chance
