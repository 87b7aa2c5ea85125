"""The reference names and flattens a model's leaves as the program does."""
import numpy as np

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
    np.testing.assert_array_equal(reference.flatten(tree),
                                  np.asarray(flatten_params(tree), np.float64))
    mlp = {"w1": np.ones(2), "b0": np.zeros(1), "w0": np.ones(3), "b1": np.zeros(2)}
    assert list(reference.named_leaves(mlp)) == ["b0", "b1", "w0", "w1"]  # sorted, as before
