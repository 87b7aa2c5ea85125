"""The reference names and flattens a model's leaves as the program does."""
import numpy as np
import pytest

import fedbench_tiny as ft  # noqa: F401  (puts the benchmark and the program on the path)
import reference


def test_leaves_are_named_by_path_in_the_programs_flatten_order():
    from repro.fl.aggregation import flatten_params

    layer = lambda i: {"w": np.full((2, 3), i, np.float32), "b": np.full(3, -i, np.float32)}
    tree = {"head": {"w": np.arange(6, dtype=np.float32)}, "body": [layer(i) for i in range(11)]}
    names = list(reference.named_leaves(tree))
    # jax.tree_util's order: dict keys sorted, list entries by index (body/10 after body/9)
    assert names[:4] == ["body/0/b", "body/0/w", "body/1/b", "body/1/w"]
    assert names[-3:] == ["body/10/b", "body/10/w", "head/w"]
    flat = np.concatenate([np.ravel(v) for v in reference.named_leaves(tree).values()])
    np.testing.assert_array_equal(flat, np.asarray(flatten_params(tree)))
    mlp = {"w1": np.ones(2), "b0": np.zeros(1), "w0": np.ones(3), "b1": np.zeros(2)}
    assert list(reference.named_leaves(mlp)) == ["b0", "b1", "w0", "w1"]  # sorted, as before


def _tiny_inputs(tmp_path, seed=2**31 + 5):
    import harness

    root = ft.make_tree(tmp_path)
    b = ft.bench(root)
    srv, inputs = harness.build_server(b.config("tiny-shards"), b.mix("md"), seed,
                                       bench_dir=root / "bench")
    srv.close()
    return inputs


class _Recording:
    """A model kind that keeps every client model its reference half returns,
    handed on as host arrays in ``order``'s memory layout."""

    def __init__(self, kind, order):
        self.kind, self.order, self.outputs = kind, order, []

    def local_train(self, dtype):
        import jax

        sgd = self.kind.local_train(dtype)

        def run(*a):
            p, loss = sgd(*a)
            p = jax.tree_util.tree_map(lambda v: np.asarray(v, order=self.order), p)
            self.outputs.append(p)
            return p, loss

        return run

    def evaluate(self, dtype):
        return self.kind.evaluate(dtype)


@pytest.mark.parametrize("block, order", [(7, "F"), (None, "C")])
def test_leaf_norms_and_rows_equal_the_stored_trees_formula(tmp_path, monkeypatch, block, order):
    """The norms and rows read as they were when every round's model was kept
    whole: an aggregate of whole float64 trees, differences of whole leaves.
    ``block`` 7 splits every leaf of the in-place aggregation into blocks;
    ``order`` is the memory layout of the initial weights and of the
    clients' results on the host (the TPU's host copies are not C-ordered)."""
    import jax

    if block is not None:
        monkeypatch.setattr(reference, "_BLOCK", block)
    inputs = _tiny_inputs(tmp_path)
    inputs["params0"] = jax.tree_util.tree_map(lambda v: np.asarray(v, order=order),
                                               inputs["params0"])
    assert inputs["params0"]["w0"].flags.f_contiguous == (order == "F")
    rec = _Recording(inputs["kind"], order)
    k = 4
    out = reference.replay(dict(inputs, kind=rec), k, rows=True)

    tree_map = jax.tree_util.tree_map
    f64 = lambda t: tree_map(lambda v: np.asarray(v, np.float64), t)
    theta, params, rows, models = f64(inputs["params0"]), [], [], iter(rec.outputs)
    params.append(theta)
    for drawn in out["clients"]:
        distinct, counts = np.unique(drawn, return_counts=True)
        w = counts / inputs["m"]
        base = f64(tree_map(lambda v: np.asarray(v, np.float32), theta))
        new, got = tree_map(lambda v: (1.0 - w.sum()) * v, theta), {}
        for i, wi in zip(distinct, w):
            p = f64(next(models))
            new = tree_map(lambda a, b: a + wi * b, new, p)
            got[int(i)] = np.concatenate(
                [np.ravel(v) for v in reference.named_leaves(tree_map(np.subtract, p, base)).values()])
        theta = new
        params.append(theta)
        rows.append(got)

    def norms(a, b):
        start, end = reference.named_leaves(params[a]), reference.named_leaves(params[b])
        return {n: np.linalg.norm(end[n] - v) for n, v in start.items()}

    assert out["update_norms"] == norms(0, 1)
    assert out["change_norms"] == norms(0, k)
    assert [list(r) for r in out["rows"]] == [list(r) for r in rows]
    for want, have in zip(rows, out["rows"]):
        for i in want:
            np.testing.assert_array_equal(have[i], want[i])
    assert reference.replay(inputs, k)["rows"] == []  # rows only when asked for


def test_a_check_holds_a_few_models_whatever_the_fleet_and_the_rounds(tmp_path):
    """MD rule, the toy kind at d = 2.1e6: the reference's host peak (numpy's
    allocations, as tracemalloc sees them) does not grow with the number of
    clients or of rounds, and stays under three float64 copies of the model."""
    import json
    import tracemalloc

    import harness
    import inputs as bench_inputs
    import toy_kind

    root = ft.make_tree(tmp_path)
    cfg = json.loads((root / "bench" / "configs" / "tiny-shards.json").read_text())
    cfg["model"] = {"kind": "toy", "width": 100_000}
    cfg["data"].update(n_classes=5, train_per_client=20, test_per_client=1)
    mix = ft.bench(root).mix("md")
    d = 16 * 100_000 + 100_000 + 100_000 * 5 + 5

    def peak(n_clients, rounds):
        cfg["data"]["clients_per_class"] = n_clients // 5
        seeds = bench_inputs.derive_seeds(2**31 + 17)
        clients = bench_inputs.make_clients(cfg["data"], seeds["data"])
        params0 = toy_kind.init_params(cfg, seeds["model"])
        ref_inputs = dict(harness.reference_inputs(cfg, mix, seeds, clients, params0),
                          kind=toy_kind)
        reference.replay(ref_inputs, 1)  # compiles this fleet's shapes
        tracemalloc.start()
        try:
            reference.replay(ref_inputs, rounds)
            return tracemalloc.get_traced_memory()[1]
        finally:
            tracemalloc.stop()

    base = peak(10, 2)
    assert peak(80, 2) <= 1.1 * base
    assert peak(10, 6) <= 1.1 * base
    assert base < 3 * 8 * d


def test_token_clients_draw_batch_rows_by_sequence_on_the_engines_stream():
    """Labels of shape (sequences, S): a client's batch rows index its
    sequences, drawn as the program's engine draws them."""
    import jax.numpy as jnp
    from repro.data.federated import ClientData
    from repro.fl.client import draw_batch_indices

    S, N, B = 11, 2, 4
    sizes = [5, 7, 3, 9]
    clients = [(np.zeros((s, S), np.int32), np.ones((s, S), np.int32),
                np.zeros((1, S), np.int32), np.ones((1, S), np.int32)) for s in sizes]
    drawn_rows = []

    class Kind:
        @staticmethod
        def local_train(dtype):
            def run(params, x, y, idx, lr):
                drawn_rows.append(np.asarray(idx))
                return params, jnp.float32(1.0)
            return run

        @staticmethod
        def evaluate(dtype):
            return lambda params, x, y: jnp.float32(0.5)

    seeds = {"sampler": 2**31 + 3, "train": 2**31 + 4}
    inputs = {"clients": clients, "params0": {"w": np.zeros(3, np.float32)}, "seeds": seeds,
              "rule": "md", "m": 3, "n_local_steps": N, "batch_size": B, "lr": 0.1,
              "kind": Kind}
    out = reference.replay(inputs, 6)

    rng = np.random.default_rng(seeds["train"])
    want = []
    for drawn in out["clients"]:
        for i in np.unique(drawn):
            n = ClientData(*clients[i]).n_train
            want.append((n, np.asarray(draw_batch_indices(rng, n, N, B))))
    assert len(drawn_rows) == len(want)
    for got, (n, rows) in zip(drawn_rows, want):
        assert got.max() < n
        np.testing.assert_array_equal(got, rows)
