"""The control and the planted faults, read at a tiny size: each is caught by
one of the check's numbers, where the sound program reads far below them."""
import pytest

import fedbench_tiny as ft


@pytest.mark.parametrize("cell", ["tiny-shards.md", "tiny-dirichlet.alg2-sync"])
def test_control_and_faults_separate_from_the_program(tmp_path, cell):
    import control

    root = ft.make_tree(tmp_path)
    out = control.readings(ft.bench(root), cell, [21, 22], {21})
    s = out["summary"]
    prog = s["program"]
    assert prog["draw_mismatch"] == 0 and prog["plan_gap"] == 0

    def separated(who, factor):
        return [k for k, v in s[who].items() if v >= factor * max(prog[k], 1e-12)]

    assert separated("control", 3), s["control"]
    assert separated("half_batch", 10), s["half_batch"]
    assert "draw_mismatch" in separated("draw_altered", 10)
    assert s["state_unchanged"]["update_gap"] == pytest.approx(1.0)
    assert s["plan_altered"]["plan_gap"] >= 1e-6
    if cell.endswith("alg2-sync"):
        assert {"store_rel", "dist_abs"} <= set(separated("control", 3))
