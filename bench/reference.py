"""Plain reference of the served FL round; the client model is the model kind's.

Written from the paper's protocol (Fraboni et al., ICML 2021, Sec. 2-5),
independent of the program:

1. the sampling plan ``r`` ((m, n), rows are distributions over clients):
   MD sampling is the plan whose every urn is the data ratios ``p``;
   Algorithm 2 builds it from the clients' representative gradients after
   every round (the cold-start plan from all-zero gradients): clients with
   ``m p_i >= 1`` get ``floor(m p_i)`` dedicated urns, the rest of their
   ``m n_i`` tokens join a pool; the pool is clustered by Ward's method on
   the angles between representative gradients, the tree is cut top-down
   into ``K >= m_pool`` groups of at most ``M`` tokens, the ``m_pool``
   heaviest groups seed one urn each and the others stream into the free
   space (:func:`algorithm2_plan`);
2. draw ``l_1..l_m``: client ``l_k`` from urn ``k`` by one uniform per urn;
3. every *distinct* drawn client runs ``N`` SGD steps of batch ``B`` from the
   global model, on the model kind's loss (``local_train``; for the MLP
   kind, ``models/mlp.py``, the softmax cross-entropy of an MLP with ReLU
   between dense layers); batch rows are drawn per client, in ascending
   client order, from the server's seeded generator;
4. the new global model is ``sum_i w_i theta_i + (1 - sum_i w_i) theta`` with
   ``w_i`` = (times drawn) / m (eq. 3/4); the round loss is the
   ``w``-weighted mean of the clients' mean step losses;
5. the representative gradient of client ``i`` is ``theta_i - theta``,
   flattened leaf by leaf in ``jax.tree_util`` leaf order (sorted keys, for
   a dict; zero until it is first drawn); the similarity is the angle
   between two of them (zero vectors: 0 to each other, pi/2 to the rest);
6. accuracy is the share of the global test set that the new model labels
   right (the kind's ``evaluate``).

Exact ties (the angles among never-drawn clients are all 0) are broken by
index, as the conventions in :func:`ward_merges` and :func:`cut` say, so
that one set of angles gives one plan.

The model is the kind's and its weights a pytree. The kind's two functions
compute in jax.numpy at the configuration's precision, one client at a
time (for the MLP: float32 with JAX's default matmul precision, which on
the TPU is one bfloat16 pass, as the program's matmuls run); aggregation,
angles and the plan are in float64 numpy, each round starting from the
float32 rounding of the aggregate, as the program's float32 model does.
``mode="bf16"`` hands the kind ``bfloat16`` as its dtype for the clients'
steps and the accuracy (the control); ``mode="half_batch"`` trains on the
first half of every batch and ``mode="frozen"`` never moves the global
model (two faults).
"""
from __future__ import annotations

import numpy as np

MODES = ("f32", "bf16", "half_batch", "frozen")


def named_leaves(tree) -> dict:
    """Leaf name -> leaf, in ``jax.tree_util`` leaf order, the order the
    program's ``flatten_params`` concatenates in. A leaf is named by its
    path's keys joined by ``/``: ``w0`` in a flat dict, ``body/0/w`` nested."""
    import jax

    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(path, simple=True, separator="/"): leaf for path, leaf in leaves}


def arccos_distances(G: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(n, d) -> ((n, n) angles, (n,) norms), in float64."""
    G = np.asarray(G, np.float64)
    gram = G @ G.T
    norms = np.sqrt(np.diag(gram))
    zero = norms == 0
    safe = np.where(zero, 1.0, norms)
    cos = gram / np.outer(safe, safe)
    cos[zero[:, None] ^ zero[None, :]] = 0.0
    cos[zero[:, None] & zero[None, :]] = 1.0
    out = np.arccos(np.clip(cos, -1.0, 1.0))
    np.fill_diagonal(out, 0.0)
    return np.maximum(out, out.T), norms


#: The sampling schemes whose plan the reference builds (a mix's sampler name).
RULES = ("md", "algorithm2")


def plan_rule(mix: dict) -> str:
    """The mix's plan rule; a mix whose plan the reference cannot build is an error."""
    name = mix["sampler"]["name"]
    planner = mix.get("planner") or {}
    if name == "algorithm2" and (planner.get("mode", "sync") != "sync"
                                 or planner.get("rebuild_every", 1) != 1
                                 or planner.get("clusterer", "ward") != "ward"
                                 or mix["sampler"].get("options")):
        raise ValueError("the reference rebuilds Algorithm 2's plan with Ward, "
                         "synchronously, after every round, and nothing else")
    if name not in RULES:
        raise ValueError(f"the reference has no plan rule for sampler {name!r}")
    return name


def ward_merges(dist: np.ndarray) -> list[tuple[int, int, float]]:
    """Ward's agglomeration of an (n, n) distance matrix.

    Returns ``n - 1`` merges ``(a, b, height)``, ``a < b``: the leaves are
    clusters ``0..n-1`` and merge ``t`` makes cluster ``n + t``. Squared
    distances follow the Lance-Williams recurrence for Ward's method,
    ``d2(k, i+j) = ((n_i + n_k) d2(k, i) + (n_j + n_k) d2(k, j) - n_k d2(i, j))
    / (n_i + n_j + n_k)``. Of tied pairs the one with the smallest first,
    then second, row of the matrix merges; the merged cluster takes the
    first row's place.
    """
    n = dist.shape[0]
    d2 = np.square(np.asarray(dist, np.float64))
    upper = np.triu(np.ones((n, n), bool), 1)  # pairs (i, j), i < j
    live = np.ones(n, bool)
    label = np.arange(n)  # cluster id held by each row
    size = np.ones(n, np.int64)
    merges = []
    for t in range(n - 1):
        pairs = np.where(upper & live[:, None] & live[None, :], d2, np.inf)
        i, j = divmod(int(np.argmin(pairs)), n)  # row-major: smallest i, then j
        dij2 = d2[i, j]
        k = live.copy()
        k[[i, j]] = False
        v = ((size[i] + size[k]) * d2[k, i] + (size[j] + size[k]) * d2[k, j]
             - size[k] * dij2) / (size[i] + size[j] + size[k])
        d2[k, i] = d2[i, k] = v
        merges.append((int(min(label[i], label[j])), int(max(label[i], label[j])),
                       float(np.sqrt(max(dij2, 0.0)))))
        size[i] += size[j]
        label[i] = n + t
        live[j] = False
    return merges


def cut(merges: list, mass: np.ndarray, m: int, capacity: int) -> list[list[int]]:
    """Cut Ward's tree top-down into ``K >= m`` groups of at most ``capacity`` tokens.

    From the root, split the first cluster (in the order the splits made
    them) whose mass is over ``capacity``; with none over, while there are
    fewer than ``m``, split the highest merge (the first of tied ones). A
    split puts the merge's two clusters, lower id first, in its place at
    the end of the order. Returns each group's clients, ascending.
    """
    n = len(merges) + 1
    children = {n + t: (a, b) for t, (a, b, _) in enumerate(merges)}
    height = {n + t: h for t, (_, _, h) in enumerate(merges)}

    def leaves(c):
        return [c] if c < n else leaves(children[c][0]) + leaves(children[c][1])

    groups = [2 * n - 2] if merges else [0]
    while True:
        over = [c for c in groups if c >= n and mass[leaves(c)].sum() > capacity]
        if over:
            c = over[0]
        elif len(groups) < m:
            c = max((c for c in groups if c >= n), key=lambda c: height[c])
        else:
            return [sorted(leaves(c)) for c in groups]
        groups.remove(c)
        groups.extend(children[c])


def algorithm2_plan(G: np.ndarray, n_train: np.ndarray, m: int) -> tuple[np.ndarray, ...]:
    """Algorithm 2's plan from the (n, d) representative gradients:
    ``(r, the pool's angles, the pool's gradient norms)``."""
    n, M = n_train.size, int(n_train.sum())
    tokens = m * n_train.astype(np.int64)
    dedicated = tokens // M
    rest = tokens - dedicated * M
    urns = np.zeros((m, n), np.int64)
    owners = np.repeat(np.arange(n), dedicated)
    urns[np.arange(owners.size), owners] = M
    pool = np.flatnonzero(rest > 0)
    dist, norms = arccos_distances(G[pool])
    if pool.size:
        groups = [pool[g] for g in cut(ward_merges(dist), rest[pool], m - owners.size, M)]
        q = [int(rest[g].sum()) for g in groups]
        order = sorted(range(len(groups)), key=lambda k: -q[k])
        seeded = order[: m - owners.size]
        for u, k in enumerate(seeded, start=owners.size):
            urns[u, groups[k]] = rest[groups[k]]
        u = owners.size
        for k in order[len(seeded):]:
            for i in groups[k]:
                left = int(rest[i])
                while left:
                    while urns[u].sum() == M:
                        u += 1
                    put = min(left, M - int(urns[u].sum()))
                    urns[u, i] += put
                    left -= put
    assert (urns.sum(axis=1) == M).all() and (urns.sum(axis=0) == tokens).all()
    return urns / M, dist, norms


def draw(plan_r: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """One client per urn: ``searchsorted`` of one uniform in the urn's CDF."""
    u = rng.random(plan_r.shape[0])
    out = np.empty(plan_r.shape[0], np.int64)
    for k, row in enumerate(np.asarray(plan_r, np.float64)):
        cdf = np.cumsum(row)
        out[k] = np.searchsorted(cdf / cdf[-1], u[k], side="right")
    return out


#: Elements per block of the in-place aggregation, so that its temporaries stay small.
_BLOCK = 1 << 18


def _add_scaled(acc: np.ndarray, w: float, leaf) -> None:
    """``acc += w * float64(leaf)`` in place, a block of the leading axis at
    a time: the same operation on every element as on the whole leaf, with
    temporaries of about ``_BLOCK`` elements. Slices of the leading axis are
    views whatever the memory layout (host copies of device arrays need not
    be C-ordered), so nothing is written to a copy."""
    b = np.asarray(leaf)
    if acc.ndim == 0:
        acc += w * b.astype(np.float64)
        return
    step = max(1, _BLOCK * acc.shape[0] // max(acc.size, 1))
    for s in range(0, acc.shape[0], step):
        acc[s:s + step] += w * b[s:s + step].astype(np.float64)


def leaf_norms(end: dict, start: dict) -> dict:
    """Leaf name -> the norm of ``end - start``, the difference taken in float64."""
    return {n: float(np.linalg.norm(np.subtract(np.asarray(end[n]), v, dtype=np.float64)))
            for n, v in start.items()}


def replay(inputs: dict, rounds: int, *, mode: str = "f32", rows: bool = False) -> dict:
    """Run ``rounds`` rounds of the protocol from the cell's inputs.

    ``inputs``: ``clients`` (list of ``(x_train, y_train, x_test, y_test)``,
    a client's samples along the leading axis), ``params0`` (a pytree of
    float32 host leaves), ``seeds`` (``sampler``, ``train``), ``rule`` (one
    of :data:`RULES`), ``m``, ``n_local_steps``, ``batch_size``, ``lr``, and
    ``kind``, the model kind whose ``local_train(dtype)`` returns ``(params,
    x, y, (N, B) rows, lr) -> (float32 params, mean step loss)`` and whose
    ``evaluate(dtype)`` returns ``(params, x_test, y_test) -> accuracy``.

    Returns per round the plan drawn from, the drawn clients, the loss and
    the accuracy; per leaf the norms of the global model's change after the
    first round (``update_norms``) and after the last (``change_norms``), by
    :func:`leaf_norms`; with ``rows``, per round every distinct client's
    representative gradient. With Algorithm 2 also per round the angles over
    the clustered pool that the next round's plan was built from and the
    pool's gradient norms, and the gradient store after the last round. Host
    memory is a few copies of the model, whatever ``rounds`` and the number
    of clients, unless a store or the rows are kept.
    """
    import jax
    import jax.numpy as jnp

    if mode not in MODES:
        raise ValueError(f"unknown reference mode {mode!r}")
    if inputs["rule"] not in RULES:
        raise ValueError(f"unknown plan rule {inputs['rule']!r}")
    dtype = "bfloat16" if mode == "bf16" else "float32"
    clients = inputs["clients"]
    n_train = np.array([len(c[1]) for c in clients])
    N, B, lr, m = inputs["n_local_steps"], inputs["batch_size"], inputs["lr"], inputs["m"]
    rng_s = np.random.default_rng(inputs["seeds"]["sampler"])
    rng_t = np.random.default_rng(inputs["seeds"]["train"])
    sgd, acc_fn = inputs["kind"].local_train(dtype), inputs["kind"].evaluate(dtype)
    x_test = jnp.asarray(np.concatenate([c[2] for c in clients]))
    y_test = jnp.asarray(np.concatenate([c[3] for c in clients]))
    params0 = named_leaves(inputs["params0"])
    treedef = jax.tree_util.tree_structure(inputs["params0"])
    theta = {n: np.asarray(v, np.float64) for n, v in params0.items()}

    def float32(tree: dict):
        return jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(v, jnp.float32) for v in tree.values()])

    start = float32(theta)
    d = sum(v.size for v in theta.values())
    store = inputs["rule"] == "algorithm2"
    G = np.zeros((len(clients), d)) if store else None
    plan_r = (algorithm2_plan(G, n_train, m)[0] if store
              else np.tile(n_train / n_train.sum(), (m, 1)))
    out = {"plans": [], "clients": [], "loss": [], "acc": [], "rows": [], "store": [],
           "dist": [], "pool_norms": []}
    for t in range(rounds):
        drawn = draw(plan_r, rng_s)
        distinct, counts = np.unique(drawn, return_counts=True)
        w = counts / m
        if mode != "frozen":
            for v in theta.values():
                v *= 1.0 - w.sum()
        base = named_leaves(jax.tree_util.tree_map(np.asarray, start)) if store or rows else None
        losses, round_rows = [], {}
        for i, wi in zip(distinct, w):
            x, y = clients[i][0], clients[i][1]
            idx = rng_t.integers(0, n_train[i], size=(N, B))
            if mode == "half_batch":
                idx = idx[:, : B // 2]
            p, loss = sgd(start, jnp.asarray(x), jnp.asarray(y), jnp.asarray(idx, jnp.int32),
                          jnp.float32(lr))
            losses.append(float(loss))
            row, off = (np.empty(d) if base is not None else None), 0
            for name, leaf in named_leaves(p).items():
                leaf = np.asarray(leaf)
                if mode != "frozen":
                    _add_scaled(theta[name], wi, leaf)
                if row is not None:
                    np.subtract(leaf.ravel(), base[name].ravel(), out=row[off:off + leaf.size],
                                dtype=np.float64)
                off += leaf.size
            if rows:
                round_rows[int(i)] = row
            if store:
                G[i] = row
        del p, base  # freed before the next round makes its own
        start = float32(theta)
        if t == 0:
            out["update_norms"] = leaf_norms(theta, params0)
        out["plans"].append(plan_r)
        out["clients"].append(drawn)
        if rows:
            out["rows"].append(round_rows)
        out["loss"].append(float(np.average(losses, weights=w)))
        out["acc"].append(float(acc_fn(start, x_test, y_test)))
        if store:
            plan_r, dist, norms = algorithm2_plan(G, n_train, m)
            out["dist"].append(dist)
            out["pool_norms"].append(norms)
    out["change_norms"] = leaf_norms(theta, params0)
    if store:
        out["store"].append(G)
    return out
