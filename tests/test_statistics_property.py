"""Property-based tests (hypothesis) of the paper's statistical theorems.

For ANY population and ANY Proposition-1-satisfying plan produced by
Algorithm 1/2:
  * eq. (17): Var_C[ω_i] <= Var_MD[ω_i]  for every client,
  * eq. (23): P_C(i ∈ S) >= P_MD(i ∈ S)  for every client,
  * both with equality iff every W_k equals W_0.
"""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import (
    ClientPopulation,
    build_plan_algorithm1,
    build_plan_algorithm2,
    validate_plan,
)
from repro.core.statistics import (
    clustered_inclusion_probability,
    clustered_weight_variance,
    md_inclusion_probability,
    md_weight_variance,
    variance_reduction,
)

populations = st.lists(st.integers(min_value=1, max_value=2000), min_size=6, max_size=60)
ms = st.integers(min_value=2, max_value=12)


@given(populations, ms)
@settings(max_examples=40, deadline=None)
def test_algorithm1_variance_and_inclusion_theorems(ns, m):
    pop = ClientPopulation(np.array(ns))
    plan = build_plan_algorithm1(pop, m)
    validate_plan(plan, pop)
    p = pop.importances

    v_md = md_weight_variance(p, m)
    v_c = clustered_weight_variance(plan)
    assert (v_c <= v_md + 1e-12).all(), "eq.(17) violated"
    assert (variance_reduction(plan, pop) >= -1e-12).all()

    q_md = md_inclusion_probability(p, m)
    q_c = clustered_inclusion_probability(plan)
    assert (q_c >= q_md - 1e-12).all(), "eq.(23) violated"


@given(populations, ms, st.integers(min_value=0, max_value=10_000))
@settings(max_examples=25, deadline=None)
def test_algorithm2_theorems_random_gradients(ns, m, seed):
    pop = ClientPopulation(np.array(ns))
    rng = np.random.default_rng(seed)
    G = rng.normal(size=(pop.n_clients, 6))
    plan = build_plan_algorithm2(pop, m, G)
    validate_plan(plan, pop)

    p = pop.importances
    assert (clustered_weight_variance(plan) <= md_weight_variance(p, m) + 1e-12).all()
    assert (
        clustered_inclusion_probability(plan) >= md_inclusion_probability(p, m) - 1e-12
    ).all()


@given(populations, ms)
@settings(max_examples=25, deadline=None)
def test_equality_iff_md(ns, m):
    """MD sampling (r_k = p ∀k) achieves exact equality in both bounds."""
    pop = ClientPopulation(np.array(ns))
    from repro.core.types import SamplingPlan

    plan = SamplingPlan(r=np.tile(pop.importances, (m, 1)))
    p = pop.importances
    np.testing.assert_allclose(clustered_weight_variance(plan), md_weight_variance(p, m))
    np.testing.assert_allclose(
        clustered_inclusion_probability(plan), md_inclusion_probability(p, m)
    )


def test_closed_form_variance_matches_monte_carlo():
    """eq. (16) against realized sampling for Algorithm 1."""
    from repro.core import Algorithm1Sampler

    pop = ClientPopulation(np.array([100, 250, 500, 750, 1000] * 4))
    m, T = 6, 6000
    s = Algorithm1Sampler(pop, m, seed=0)
    ws = np.stack([s.sample(t).agg_weights for t in range(T)])
    theory = clustered_weight_variance(s.plan)
    mc = ws.var(axis=0)
    np.testing.assert_allclose(mc, theory, atol=5e-4)


def test_distinct_clients_probability_paper_number():
    """Section 6: with n=100 uniform, m=10, P(10 distinct) ≈ 63% for MD."""
    from repro.core.statistics import md_prob_all_distinct

    p = md_prob_all_distinct(np.full(100, 0.01), 10)
    assert abs(p - 0.6282) < 1e-3


# --------------------------------------------------------------------------
# availability-conditioned unbiasedness (the continuous-service extension)
# --------------------------------------------------------------------------
masks = st.integers(min_value=0, max_value=10_000)


def _conditional_expected_weights(plan, a):
    """E[ω_i | available] under the conditional draw, in closed form.

    Urn k draws client i w.p. r̃_ki = r_ki·a_i/s_k and contributes weight
    w_k = s_k/Σ_j s_j, so E[ω_i] = Σ_k w_k·r̃_ki = Σ_k r_ki·a_i / Σ_j s_j.
    """
    from repro.core.samplers.base import conditional_plan

    r_cond, w = conditional_plan(plan, a)
    return (w[:, None] * r_cond).sum(axis=0)


def _random_mask(n, seed, p_avail=0.6):
    rng = np.random.default_rng(seed)
    a = rng.random(n) < p_avail
    if not a.any():
        a[rng.integers(n)] = True
    return a


@given(populations, ms, masks)
@settings(max_examples=30, deadline=None)
def test_availability_conditioned_unbiasedness_algorithm1(ns, m, seed):
    """For ANY eq.(8)-satisfying plan and ANY availability mask, the
    importance-corrected conditional draw is unbiased over the available
    set: E[ω_i | available] = p_i·a_i / Σ_j p_j·a_j exactly."""
    pop = ClientPopulation(np.array(ns))
    plan = build_plan_algorithm1(pop, m)
    a = _random_mask(pop.n_clients, seed)
    expect = _conditional_expected_weights(plan, a)
    p = pop.importances
    target = p * a / (p * a).sum()
    np.testing.assert_allclose(expect, target, atol=1e-12)
    assert (expect[~a] == 0).all()
    np.testing.assert_allclose(expect.sum(), 1.0, atol=1e-12)


@given(populations, ms, masks)
@settings(max_examples=20, deadline=None)
def test_masked_rebuild_keeps_eq8_and_stays_unbiased(ns, m, seed):
    """Availability-restricted rebuilds (``cluster_mask``) for ANY mask:
    masked-out pool clients ride filler chunks instead of the similarity
    clustering, but their integer token mass is untouched — eq. (8) holds
    *exactly*, so the conditional draw stays exactly unbiased over any
    (independent) availability mask at draw time."""
    pop = ClientPopulation(np.array(ns))
    rng = np.random.default_rng(seed)
    G = rng.normal(size=(pop.n_clients, 6))
    cmask = _random_mask(pop.n_clients, seed + 7, p_avail=0.5)
    plan = build_plan_algorithm2(pop, m, G, cluster_mask=cmask)
    validate_plan(plan, pop)
    # exact integer eq.(8): column sums m·n_i, every urn holds M tokens
    np.testing.assert_array_equal(plan.r_tokens.sum(axis=0), m * pop.n_samples)
    np.testing.assert_array_equal(
        plan.r_tokens.sum(axis=1), np.full(m, pop.total_samples)
    )
    # only masked-in clients may carry similarity-cluster labels (except
    # the degenerate masks — all-in / no masked-in pool client — where the
    # build falls back to clustering the whole pool)
    mass = m * pop.n_samples
    pool = np.flatnonzero(mass % pop.total_samples > 0)
    if not cmask.all() and cmask[pool].any():
        assert (plan.cluster_of[~cmask] == -1).all()
    # the draw-time availability mask is independent of the rebuild mask
    a = _random_mask(pop.n_clients, seed + 13)
    p = pop.importances
    target = p * a / (p * a).sum()
    np.testing.assert_allclose(
        _conditional_expected_weights(plan, a), target, atol=1e-12
    )


@given(populations, ms, masks)
@settings(max_examples=20, deadline=None)
def test_availability_conditioned_unbiasedness_algorithm2_and_md(ns, m, seed):
    from repro.core.types import SamplingPlan

    pop = ClientPopulation(np.array(ns))
    rng = np.random.default_rng(seed)
    G = rng.normal(size=(pop.n_clients, 6))
    a = _random_mask(pop.n_clients, seed + 1)
    p = pop.importances
    target = p * a / (p * a).sum()
    for plan in (
        build_plan_algorithm2(pop, m, G),
        SamplingPlan(r=np.tile(p, (m, 1))),  # MD: all rows equal p
    ):
        np.testing.assert_allclose(
            _conditional_expected_weights(plan, a), target, atol=1e-12
        )


def test_conditional_draw_monte_carlo_matches_expectation():
    """The realized masked draw (importance-corrected urn weights) agrees
    with the closed-form conditional expectation."""
    from repro.core import Algorithm1Sampler

    pop = ClientPopulation(np.array([100, 250, 500, 750, 1000] * 3))
    m, T = 6, 8000
    s = Algorithm1Sampler(pop, m, seed=0)
    a = _random_mask(pop.n_clients, seed=5)
    ws = np.stack([s.sample(t, a).agg_weights for t in range(T)])
    np.testing.assert_allclose(ws.sum(axis=1), 1.0, atol=1e-12)  # mass conserved
    assert (ws[:, ~a] == 0).all()  # never draws the unavailable
    expect = _conditional_expected_weights(s.plan, a)
    np.testing.assert_allclose(ws.mean(axis=0), expect, atol=5e-3)


# --------------------------------------------------------------------------
# the scheme zoo: every stratified-family plan is an exact eq.(7)/(8) plan,
# so ALL the above theorems transfer; importance owns eq.(12) at draw time
# --------------------------------------------------------------------------
def _exact_expected_weights(plan):
    """E[ω_i] of the unconditional draw: Σ_k r_ki / m (eq. 12, closed form)."""
    return plan.r.sum(axis=0) / plan.m


@given(populations, ms, st.integers(min_value=0, max_value=10_000))
@settings(max_examples=25, deadline=None)
def test_stratified_and_hybrid_plans_satisfy_all_theorems(ns, m, seed):
    """For ANY population and gradients: stratified & hybrid plans pass the
    exact Proposition-1 check, are exactly unbiased (E[ω_i] = p_i, sum-to-one
    support included), and never exceed MD's weight variance (eq. 17)."""
    from repro.core import build_plan_hybrid, build_plan_stratified

    pop = ClientPopulation(np.array(ns))
    rng = np.random.default_rng(seed)
    G = rng.normal(size=(pop.n_clients, 6))
    p = pop.importances
    for build in (build_plan_stratified, build_plan_hybrid):
        plan = build(pop, m, G, seed=seed)
        validate_plan(plan, pop)  # exact: integer tokens, eq.(7) + eq.(8)
        np.testing.assert_allclose(_exact_expected_weights(plan), p, atol=1e-12)
        np.testing.assert_allclose(plan.r.sum(axis=1), 1.0, atol=1e-12)
        assert (clustered_weight_variance(plan) <= md_weight_variance(p, m) + 1e-12).all()


@given(populations, ms, masks)
@settings(max_examples=20, deadline=None)
def test_stratified_and_hybrid_availability_conditioned_unbiasedness(ns, m, seed):
    """Under ANY availability mask the conditional draw of a stratified /
    hybrid plan hits the eq.(8) conditional target exactly — no new code
    path: conditional_plan works off eq.(8) alone."""
    from repro.core import build_plan_hybrid, build_plan_stratified

    pop = ClientPopulation(np.array(ns))
    rng = np.random.default_rng(seed)
    G = rng.normal(size=(pop.n_clients, 6))
    a = _random_mask(pop.n_clients, seed + 1)
    p = pop.importances
    target = p * a / (p * a).sum()
    for build in (build_plan_stratified, build_plan_hybrid):
        expect = _conditional_expected_weights(build(pop, m, G, seed=seed), a)
        np.testing.assert_allclose(expect, target, atol=1e-12)
        assert (expect[~a] == 0).all()


@given(populations, ms, st.integers(min_value=0, max_value=10_000))
@settings(max_examples=15, deadline=None)
def test_dp_stratified_plans_stay_exactly_unbiased(ns, m, seed):
    """ANY noise level: the DP plan is still an exact eq.(7)/(8) plan —
    noise moves strata membership, never the allocation."""
    from repro.core import DPStratifiedSampler

    pop = ClientPopulation(np.array(ns))
    rng = np.random.default_rng(seed)
    s = DPStratifiedSampler(
        pop, m, 6, noise_multiplier=float(10.0 ** (seed % 5 - 2)), seed=seed
    )
    try:
        s.observe_updates(
            np.arange(pop.n_clients),
            rng.normal(size=(pop.n_clients, 6)).astype(np.float32),
        )
        s.sample(0)  # sync swap-in of the noised-strata plan
        plan = s.plan
    finally:
        s.close()
    validate_plan(plan, pop)
    np.testing.assert_allclose(_exact_expected_weights(plan), pop.importances, atol=1e-12)
    a = _random_mask(pop.n_clients, seed + 1)
    p = pop.importances
    np.testing.assert_allclose(
        _conditional_expected_weights(plan, a), p * a / (p * a).sum(), atol=1e-12
    )


@given(populations, ms, masks, st.floats(min_value=0.05, max_value=1.0))
@settings(max_examples=20, deadline=None)
def test_importance_expected_weights_exact(ns, m, seed, mix):
    """Importance sampling's draw-time bookkeeping is exactly unbiased for
    ANY proposal the mix floor can produce, unconditionally AND under any
    availability mask: E[ω_i] = p_i and E[ω_i | a] = p_i·a_i / Σ_j p_j·a_j.

    Closed form: per urn, client i is drawn w.p. q_i (masked: q_i·a_i/Σq·a)
    and carries weight (1/m)·c_i with c the sampler's correction, so
    E[ω_i] = q_i·c_i (masked: weight w_k·c_i with w_k = Σq·a/… folded
    into the correction's availability ratio).
    """
    from repro.core import ImportanceSampler

    pop = ClientPopulation(np.array(ns))
    rng = np.random.default_rng(seed)
    s = ImportanceSampler(pop, m, 6, mix=float(mix), seed=seed)
    try:
        s.observe_updates(
            np.arange(pop.n_clients),
            rng.normal(size=(pop.n_clients, 6)).astype(np.float32),
        )
        s.sample(0)  # swap in the norm-tilted proposal
        q = s.plan.r[0]
        p = pop.importances
        # unconditional: E[ω_i] = m·q_i·(1/m)·(p_i/q_i) = p_i exactly
        np.testing.assert_allclose(q * s.correction(), p, atol=1e-12)
        # masked: m urns × draw prob (q_i·a_i/Σq·a) × weight (1/m)·c_i
        a = _random_mask(pop.n_clients, seed + 1)
        qa = (q * a).sum()
        expect = (q * a / qa) * s.correction(a)
        np.testing.assert_allclose(expect, p * a / (p * a).sum(), atol=1e-12)
    finally:
        s.close()
