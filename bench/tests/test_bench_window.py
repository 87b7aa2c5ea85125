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

    srv, _ = _server(tree, "tiny-shards.md")
    cap = harness.check_rounds(srv, 3)
    assert len(cap.loss) == len(cap.clients) == len(cap.plans) == len(cap.params) == 3
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

    srv, _ = _server(tree, "tiny-shards.md")
    harness.check_rounds(srv, 1)
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

    srv, _ = _server(tree, "tiny-dirichlet.alg2-sync")
    harness.check_rounds(srv, 2)
    params = {k: np.asarray(v) for k, v in srv.params.items()}
    store = srv.sampler.gradient_store.asnumpy()
    harness.warm_up(srv)
    for k, v in params.items():
        np.testing.assert_array_equal(np.asarray(srv.params[k]), v)
    np.testing.assert_array_equal(srv.sampler.gradient_store.asnumpy(), store)
    srv.close()
