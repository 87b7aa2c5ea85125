#!/usr/bin/env bash
# Tier-1 verification: the full test suite + the spec, sweep, scheme-race and
# service smoke runs.
#
# Collection errors (missing optional deps, jax API drift) take down whole
# test modules silently under plain `pytest path` invocations — this script
# is the one entry point CI and humans share, so such regressions fail fast.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1: pytest =="
python -m pytest -q

echo "== tier-1: fused streamed kernel parity vs numpy (ragged-chunk shape) =="
# 13x101 is ragged against both the 8-row and 16-column tiles AND the
# 32-wide d-chunk — the in-kernel masking path the fused grid must get right
python - <<'EOF'
import numpy as np
from repro.core.clustering import pairwise_distances
from repro.kernels.similarity.ops import pairwise_distances_streamed
rng = np.random.default_rng(0)
G = rng.normal(size=(13, 101)).astype(np.float32)
for measure in ("arccos", "l2", "l1"):
    ref = pairwise_distances(G, measure)
    fused = np.asarray(pairwise_distances_streamed(
        G, measure, block_n=8, block_d=16, d_chunk=32, interpret=True))
    np.testing.assert_allclose(fused, ref, atol=1e-4, err_msg=measure)
print("fused streamed == numpy reference (13x101 ragged, all measures)")
EOF

echo "== tier-1: identity-sketch == legacy store bit-parity gate =="
# sketch="identity" engages the sketch stage yet must train byte-for-byte
# like the store with no sketch stage at all (plan_build_ms is wall-clock
# telemetry and is normalized out, exactly as in tests/test_service_resume)
python - <<'EOF'
import json
from repro.fl.experiment import ExperimentSpec, build_experiment

BASE = {
    "data": {"name": "by_class_shards",
             "options": {"n_classes": 4, "clients_per_class": 2, "dim": 8,
                          "train_per_client": 40, "test_per_client": 8, "seed": 0}},
    "sampler": {"name": "algorithm2", "m": 4, "seed": 3},
    "train": {"n_rounds": 4, "n_local_steps": 3, "batch_size": 10, "hidden": [16]},
}

def run(planner):
    spec = ExperimentSpec.from_dict({**BASE, **({"planner": planner} if planner else {})})
    with build_experiment(spec) as srv:
        recs = json.loads(srv.run().to_json())
    for r in recs:
        r["plan_build_ms"] = -1.0
    return json.dumps(recs)

assert run(None) == run({"sketch": "identity"}), "identity sketch broke bit-parity"
print("identity sketch == legacy store (bit-identical history)")
EOF

echo "== tier-1: sweep smoke (2 cells x 2 seeds, then resume on the same store) =="
SWEEP_STORE="$(mktemp -d)"
trap 'rm -rf "$SWEEP_STORE"' EXIT
SWEEP_JSON='{
  "base": {"data": {"name": "by_class_shards",
                    "options": {"n_classes": 4, "clients_per_class": 3, "dim": 8,
                                "train_per_client": 40, "test_per_client": 8}},
           "sampler": {"name": "md", "m": 4},
           "train": {"n_rounds": 3, "n_local_steps": 4, "batch_size": 16, "hidden": [16]}},
  "axes": {"sampler.name": ["md", "algorithm1"]},
  "n_seeds": 2,
  "root_seed": 7
}'
python -m benchmarks.run --sweep "$SWEEP_JSON" --store "$SWEEP_STORE"
# re-invoking the same store must resume (all 4 cells skip, collation intact)
python -m benchmarks.run --sweep "$SWEEP_JSON" --store "$SWEEP_STORE" \
  | tee /dev/stderr | grep -c "status=skipped" | grep -qx 4
test -s "$SWEEP_STORE/cells.csv" && test -s "$SWEEP_STORE/summary.csv"

echo "== tier-1: scheme race smoke (2 schemes x 2 seeds, then resume) =="
RACE_STORE="$(mktemp -d)"
trap 'rm -rf "$SWEEP_STORE" "$RACE_STORE"' EXIT
python -m benchmarks.scheme_race --smoke --store "$RACE_STORE"
# re-invoking the same store must resume (all 4 cells skip, collation intact)
python -m benchmarks.scheme_race --smoke --store "$RACE_STORE" \
  | tee /dev/stderr | grep -c "status=skipped" | grep -qx 4
test -s "$RACE_STORE/summary.csv"
# summary.csv must carry the race columns (time-to-accuracy + weight variance)
head -1 "$RACE_STORE/summary.csv" | grep -q "rounds_to_acc_mean"
head -1 "$RACE_STORE/summary.csv" | grep -q "agg_weight_var_mean"

echo "== tier-1: md == importance(mix=1.0) bit-parity gate =="
# importance with a size-proportional proposal (mix=1.0) must train
# byte-for-byte like md — the scheme zoo's degenerate-case anchor
python -m benchmarks.scheme_race --parity

echo "== tier-1: registry discoverability (--list) =="
python -m benchmarks.run --list

echo "== tier-1: spec-driven experiment smoke (registry + spec parsing) =="
python -m benchmarks.run --spec '{
  "data": {"name": "by_class_shards",
           "options": {"n_classes": 4, "clients_per_class": 3, "dim": 8,
                        "train_per_client": 40, "test_per_client": 8, "seed": 0}},
  "sampler": {"name": "algorithm2", "m": 4},
  "planner": {"mode": "async", "rebuild_every": 2},
  "train": {"n_rounds": 3, "n_local_steps": 4, "batch_size": 16, "hidden": [16]}
}'

echo "== tier-1: continuous-service smoke (SIGTERM mid-campaign, then resume) =="
SVC_DIR="$(mktemp -d)"
trap 'rm -rf "$SWEEP_STORE" "$RACE_STORE" "$SVC_DIR"' EXIT
SVC_SPEC='{
  "data": {"name": "by_class_shards",
           "options": {"n_classes": 4, "clients_per_class": 2, "dim": 8,
                        "train_per_client": 40, "test_per_client": 8, "seed": 0}},
  "sampler": {"name": "algorithm1", "m": 4},
  "train": {"n_rounds": 30, "n_local_steps": 3, "batch_size": 10,
            "hidden": [16], "checkpoint_every": 1},
  "population": {"name": "dropout", "options": {"rate": 0.2}}
}'
python -m repro.launch.fl_service --spec "$SVC_SPEC" \
  --checkpoint "$SVC_DIR/svc.npz" --history "$SVC_DIR/history.json" \
  --throttle 0.2 > "$SVC_DIR/run1.log" 2>&1 &
SVC_PID=$!
sleep 4  # throttled rounds: the campaign is guaranteed still mid-flight
kill -TERM "$SVC_PID"
wait "$SVC_PID"  # SIGTERM must be a clean exit (checkpoint written, rc 0)
grep -q "stop requested" "$SVC_DIR/run1.log"
# NOTE: log to a file and grep afterwards — piping the live process into
# `grep -q` would close its stdout on first match and cut the campaign short.
python -m repro.launch.fl_service --spec "$SVC_SPEC" \
  --checkpoint "$SVC_DIR/svc.npz" --history "$SVC_DIR/history.json" --resume \
  > "$SVC_DIR/run2.log" 2>&1
grep -q "resuming at round" "$SVC_DIR/run2.log"
# the resumed history must extend the checkpointed cursor to all 30 rounds,
# contiguously from 0 — no gap and no replay at the kill point
python - "$SVC_DIR/history.json" <<'EOF'
import json, sys
recs = json.load(open(sys.argv[1]))
rounds = [r["round"] for r in recs]
assert rounds == list(range(30)), f"history not contiguous 0..29: {rounds}"
assert any(r["round_status"] == "degraded" for r in recs), "dropout never degraded a round"
EOF

echo "tier-1 OK"
