"""The window loop at a tiny size on the CPU: warm-up, the stop, stamps, compiles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fedbench_tiny as ft


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return ft.make_tree(tmp_path_factory.mktemp("bench"))


def _server(tree, cell):
    b = ft.bench(tree)
    c = b.cell(cell)
    import harness

    return harness.build_server(b.config(c["config"]), b.mix(c["traffic"]), 2**31 + 99)


def test_checked_rounds_are_recorded_and_the_window_compiles_nothing(tree):
    import harness

    srv, inputs = _server(tree, "tiny-shards.md")
    cap = harness.check_rounds(srv, 3, inputs["params0"])
    assert len(cap.loss) == len(cap.clients) == len(cap.plans) == len(cap.rows) == 3
    assert list(cap.update_norms) == list(cap.change_norms) == ["b0", "b1", "w0", "w1"]
    assert "sample" not in vars(srv.sampler)  # the recording wrappers are gone
    harness.warm_up(srv)
    win = harness.run_window(srv, 0.5)
    n = len(win["stamps"])
    assert n >= 1 and win["compiles"] == 0
    assert np.all(np.diff([win["start"]] + win["stamps"]) > 0)
    assert win["end"] >= win["start"] + 0.5
    # should_stop ends the run at the first round boundary after the deadline
    if n > 1:
        assert win["stamps"][-2] < win["start"] + 0.5 <= win["stamps"][-1]
    e2e = harness.end_to_end(win)
    assert e2e["round_ms"] > 0 and e2e["round_p95_ms"] > 0
    srv.close()


def test_a_compile_inside_the_window_is_counted(tree):
    import harness

    srv, inputs = _server(tree, "tiny-shards.md")
    harness.check_rounds(srv, 1, inputs["params0"])
    harness.warm_up(srv)
    run_round = srv.run_round
    shapes = iter(range(3, 1000))

    def with_a_new_program(t):
        jax.jit(lambda x: x * 2)(jnp.ones(next(shapes))).block_until_ready()
        return run_round(t)

    srv.run_round = with_a_new_program
    win = harness.run_window(srv, 0.3)
    assert win["compiles"] >= len(win["stamps"]) >= 1
    srv.close()


def test_warm_up_leaves_the_model_and_the_store_as_they_were(tree):
    import harness

    srv, inputs = _server(tree, "tiny-dirichlet.alg2-sync")
    harness.check_rounds(srv, 2, inputs["params0"])
    params = {k: np.asarray(v) for k, v in srv.params.items()}
    store = srv.sampler.gradient_store.asnumpy()
    harness.warm_up(srv)
    for k, v in params.items():
        np.testing.assert_array_equal(np.asarray(srv.params[k]), v)
    np.testing.assert_array_equal(srv.sampler.gradient_store.asnumpy(), store)
    srv.close()


def test_a_round_without_rows_is_checked_without_rows_rel(tree):
    """An engine that returns no (c, d) rows: the capture keeps none, the
    warm-up compiles the dispatch alone, the check has no ``rows_rel``, and
    a limits file that names ``rows_rel`` then fails."""
    import math

    import check
    import harness
    import reference

    srv, inputs = _server(tree, "tiny-shards.md")
    k = 2
    with_rows = harness.check_rounds(srv, k, inputs["params0"])
    local_work = srv._phase_local_work
    srv._phase_local_work = lambda *a: (lambda out: (out[0], None, out[2]))(local_work(*a))
    cap = harness.Capture(srv, inputs["params0"])
    srv._phase_local_work(np.arange(2), np.full(2, 0.5), 0.0)
    cap.close()
    assert cap.rows == [] and set(cap.update_norms) == set(cap.change_norms) == {
        "b0", "b1", "w0", "w1"}
    harness.warm_up(srv)
    srv.close()

    prog = dict(check.program_record(with_rows), rows=[])
    values = check.numbers(prog, reference.replay(inputs, k))
    assert "rows_rel" not in values
    assert {"update_gap", "change_gap", "loss_rel", "acc_gap"} <= set(values)
    limits = ft.bench(tree).limits("tiny-shards.md")
    ok, table = check.verdict(values, limits)
    assert not ok and math.isnan(table["rows_rel"]["value"])
    assert check.verdict(values, {n: v for n, v in limits.items() if n != "rows_rel"})[0]
