"""Urn-filling allocator invariants (Appendix C)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.allocation import allocate_by_groups, allocate_by_size, fill_urns_sequential


@given(
    st.lists(st.integers(min_value=1, max_value=500), min_size=4, max_size=40),
    st.integers(min_value=2, max_value=8),
)
@settings(max_examples=50, deadline=None)
def test_allocate_by_size_invariants(ns, m):
    ns = np.array(ns)
    M = int(ns.sum())
    tokens = allocate_by_size(m * ns, n_urns=m, capacity=M)
    # every urn holds exactly M tokens (eq. 7 after normalization)
    assert (tokens.sum(axis=1) == M).all()
    # every client allocated exactly m*n_i tokens (eq. 8)
    assert (tokens.sum(axis=0) == m * ns).all()
    # contiguity: nonzero urns of a client form a contiguous range
    for i in range(len(ns)):
        nz = np.flatnonzero(tokens[:, i])
        assert (np.diff(nz) == 1).all() if len(nz) > 1 else True


def test_sequential_filling_overflow_raises():
    with pytest.raises(ValueError):
        fill_urns_sequential([(0, 11)], n_clients=1, n_urns=2, capacity=5)


def test_group_allocation_seeds_largest_groups():
    ns = np.full(12, 10)
    m = 3
    M = int(ns.sum())  # 120; per-client mass m*n_i = 30 -> <= 4 clients/group
    groups = [np.arange(0, 4), np.arange(4, 8), np.arange(8, 10), np.arange(10, 12)]
    tokens = allocate_by_groups(m * ns, m, M, groups)
    assert (tokens.sum(axis=1) == M).all()
    assert (tokens.sum(axis=0) == m * ns).all()
    # group 0 (a largest group) seeds one urn: its clients share an urn
    urn_of_g0 = np.flatnonzero(tokens[:, 0])
    for i in range(4):
        assert tokens[urn_of_g0, i].sum() > 0


def test_group_over_capacity_rejected():
    ns = np.array([10, 10, 1, 1])
    m = 2
    M = int(ns.sum())
    with pytest.raises(ValueError):
        allocate_by_groups(m * ns, m, M, [np.array([0, 1]), np.array([2]), np.array([3])])
