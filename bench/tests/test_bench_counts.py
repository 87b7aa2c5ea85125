"""Operations and bytes from shapes, and the table of peaks."""
import json

import pytest

import counts
import fedbench_tiny as ft


def test_counts_at_the_fig1_shapes():
    dims = (784, 50, 10)
    d = counts.mlp_param_count(dims)
    assert d == 39_760
    assert counts.round_train_flops(d, 10, 10, 50) == 6 * 39_760 * 5_000 == 1_192_800_000
    assert counts.similarity_flops(100, d) == 2 * 100 * 100 * d
    assert counts.similarity_bytes(100, d) == 4 * (100 * d + 100 * 100)
    least, bound = counts.roofline_seconds(counts.similarity_flops(100, d),
                                           counts.similarity_bytes(100, d), 197e12, 819e9)
    assert bound == "memory" and least == pytest.approx(15_944_000 / 819e9)


def test_counts_at_the_fig2_shapes():
    assert counts.mlp_param_count((3072, 50, 10)) == 154_160


def test_peaks_are_keyed_by_device_kind():
    import harness

    table = json.loads((ft.BENCH_DIR / "peaks.json").read_text())
    assert harness.load_peaks("TPU v5 lite") == table["TPU v5 lite"]
    assert table["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        harness.load_peaks("cpu")
