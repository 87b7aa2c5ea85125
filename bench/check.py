"""The numbers that decide ``correct``: the program's first rounds against the reference.

Each number is a reading of one guarantee over the checked rounds:

* ``plan_gap``: the largest gap between an entry of a plan the program drew
  from and the same entry of the plan the reference built for that round
  (the reference's plans meet Proposition 1 by construction);
* ``draw_mismatch``: urn draws in which the program's client differs from
  the reference's (exact; limit 0);
* ``loss_rel``: the largest relative gap of a round's train loss;
* ``update_gap``: the first round's change of the global model, by the worst
  leaf: ``| |d_prog| - |d_ref| |`` over ``max(|d_ref|, median leaf |d_ref|)``,
  leaves named by their pytree path (:func:`reference.named_leaves`); each
  side records only these per-leaf norms (:func:`reference.leaf_norms`);
* ``change_gap``: the same for the change over all checked rounds;
* ``acc_gap``: the largest gap of a round's test accuracy;
* ``rows_rel`` (where the program's rounds return rows): the largest
  relative distance of a client's representative gradient (``theta_i -
  theta``, the engine's per-client output) from the reference's;
* ``store_rel`` (plans built from the gradient store): the same for the rows
  of the store after the last checked round;
* ``dist_abs`` (same): the largest gap, in radians, of an angle the plan
  rebuild computed.

Leaves whose reference change is under a thousandth of the median leaf's
are left out of the two gaps of norms: they move by round-off alone. In
the same way a representative gradient under a thousandth of the median
one is left out of ``rows_rel``, ``store_rel`` and ``dist_abs``: a client
that already fits its data moves by round-off, and its direction is noise
in any precision. Rows that are exactly zero (clients not yet drawn) stay
in the angles.
"""
from __future__ import annotations

import math

import numpy as np


def _leaf_gap(prog_norms: dict, ref_norms: dict) -> float:
    names = list(ref_norms)
    ref = np.array([ref_norms[k] for k in names])
    got = np.array([prog_norms[k] for k in names])
    median = float(np.median(ref))
    keep = ref >= 1e-3 * median
    if not keep.any():
        return math.inf
    return float((np.abs(got - ref) / np.maximum(ref, median))[keep].max())


def _sound(norms: np.ndarray) -> np.ndarray:
    """Rows at or above a thousandth of the median non-zero row norm."""
    live = norms[norms > 0]
    return norms >= 1e-3 * float(np.median(live)) if live.size else norms > 0


def _row_norm(row: np.ndarray) -> float:
    """``np.linalg.norm(rows, axis=1)``'s reading of one row, taken alone."""
    return np.linalg.norm(row[None], axis=1)[0]


def _gap_norm(got: np.ndarray, want: np.ndarray) -> float:
    """:func:`_row_norm` of ``got - want`` (``got`` widened to float64),
    squared in place: the same products and sums, one temporary row."""
    diff = np.subtract(got, want, dtype=np.float64)
    return np.sqrt(np.add.reduce(np.square(diff, out=diff)[None], axis=1))[0]


def _rows_rel(got, want) -> float:
    """Over rows (float64 ``want``, ``got`` in any float dtype, widened),
    one pair at a time, so no second copy of all the rows is made."""
    norms = np.array([_row_norm(np.asarray(w, np.float64)) for w in want])
    gaps = np.array([_gap_norm(g, w) for g, w in zip(got, want)])
    keep = _sound(norms)
    if not keep.any():
        return math.inf
    return float((gaps[keep] / norms[keep]).max())


def numbers(prog: dict, ref: dict) -> dict:
    """The compared numbers of one run; ``prog`` and ``ref`` hold the same keys
    as :func:`reference.replay`'s result."""
    k = len(ref["loss"])
    if len(prog["loss"]) != k:
        return {"rounds_missing": float(k - len(prog["loss"]))}
    out = {
        "draw_mismatch": float(sum(int((np.asarray(a) != b).sum())
                                   for a, b in zip(prog["clients"], ref["clients"]))),
        "plan_gap": max(float(np.abs(np.asarray(a, np.float64) - b).max())
                        for a, b in zip(prog["plans"], ref["plans"])),
        "loss_rel": max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"])),
        "update_gap": _leaf_gap(prog["update_norms"], ref["update_norms"]),
        "change_gap": _leaf_gap(prog["change_norms"], ref["change_norms"]),
        "acc_gap": max(abs(a - b) for a, b in zip(prog["acc"], ref["acc"])),
    }
    if prog["rows"]:
        gaps = [_rows_rel([got[i] for i in want], list(want.values())) if set(got) == set(want)
                else math.inf for got, want in zip(prog["rows"], ref["rows"])]
        out["rows_rel"] = max(gaps) if len(prog["rows"]) == len(ref["rows"]) == k else math.inf
    if ref["store"]:
        if not prog["store"] or len(prog["dist"]) != len(ref["dist"]):
            return dict(out, store_rel=math.inf, dist_abs=math.inf)
        out["store_rel"] = _rows_rel(prog["store"][-1], ref["store"][-1])
        gaps = []
        for got, want, norms in zip(prog["dist"], ref["dist"], ref["pool_norms"]):
            keep = _sound(norms) | (norms == 0)
            gap = np.abs(np.asarray(got, np.float64) - want)[np.ix_(keep, keep)]
            gaps.append(float(gap.max()))
        out["dist_abs"] = max(gaps)
    return out


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and ``{name: {"value", "limit"}}``. A number without a limit,
    a limit without its number, or a value that is not finite fails."""
    table, ok = {}, True
    for name in sorted(set(values) | set(limits)):
        value, limit = values.get(name, math.nan), limits.get(name, math.nan)
        table[name] = {"value": value, "limit": limit}
        ok = ok and math.isfinite(value) and math.isfinite(limit) and value <= limit
    return ok, table


def program_record(capture) -> dict:
    """The capture of the program's checked rounds, in :func:`reference.replay`'s shape."""
    return {
        "clients": capture.clients,
        "plans": capture.plans,
        "update_norms": capture.update_norms,
        "change_norms": capture.change_norms,
        "loss": capture.loss,
        "acc": capture.acc,
        "rows": capture.rows,
        "store": [] if capture.store is None else [capture.store],
        "dist": capture.dist,
    }

