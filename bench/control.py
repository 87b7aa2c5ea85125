"""Readings that the limits of a cell's check are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,... --control-seeds 1,2,3

For every seed: the set-up of a run (inputs, server, the checked rounds
through ``FederatedServer.run``) and the check's numbers, program against
reference: the lower readings. For each control seed, the same numbers of
what stands in the program's place:

* ``control``: the reference with parameters and activations in bfloat16,
  the precision below the configuration's (for the MLP, float32 at the
  default matmul precision, whose one bfloat16 pass is already the
  program's), which the model kind's reference half is handed as its dtype;
* ``half_batch``: the reference trained on half of every batch, the mean
  taken over the rest;
* ``state_unchanged``: the reference with the global model never moving;
* ``draw_altered``: the program's record with its first drawn client replaced;
* ``plan_altered``: the program's record with one token of the first urn of
  its last plan moved from one client to another.

The benchmark's runs do not run this; it is kept to set and re-read limits.
Prints one JSON object: the readings per seed, the largest program reading
and the smallest reading of each stand-in, per number.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR.parent / "src"))

import numpy as np  # noqa: E402

import check  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402


def stand_ins(prog: dict, ref_inputs: dict, rounds: int) -> dict:
    """Records of what stands in the program's place, keyed by name; the
    reference's stand-ins keep rows where the program's record has them."""
    out = {}
    for mode, name in (("bf16", "control"), ("half_batch", "half_batch"),
                       ("frozen", "state_unchanged")):
        out[name] = reference.replay(ref_inputs, rounds, mode=mode, rows=bool(prog["rows"]))
    n = len(ref_inputs["clients"])
    drawn = [c.copy() for c in prog["clients"]]
    drawn[0][0] = (drawn[0][0] + 1) % n
    out["draw_altered"] = dict(prog, clients=drawn)
    plan = np.array(prog["plans"][-1], np.float64)
    token = 1.0 / sum(len(c[1]) for c in ref_inputs["clients"])
    src = int(np.argmax(plan[0]))
    plan[0, src] -= token
    plan[0, (src + 1) % n] += token
    out["plan_altered"] = dict(prog, plans=prog["plans"][:-1] + [plan])
    return out


def readings(bench, cell_name: str, seeds, control_seeds) -> dict:
    cell = bench.cell(cell_name)
    cfg, mix = bench.config(cell["config"]), bench.mix(cell["traffic"])
    per_seed = {}
    for seed in seeds:
        t0 = time.perf_counter()
        srv, ref_inputs = harness.build_server(cfg, mix, seed, bench_dir=bench.dir)
        rounds = harness.rounds_checked(ref_inputs["kind"], cfg, len(srv.dataset.clients))
        cap = harness.check_rounds(srv, rounds, ref_inputs["params0"])
        srv.close()
        del srv
        gc.collect()
        prog = check.program_record(cap)
        ref = reference.replay(ref_inputs, rounds, rows=bool(prog["rows"]))
        row = {"program": check.numbers(prog, ref)}
        if seed in control_seeds:
            for name, rec in stand_ins(prog, ref_inputs, rounds).items():
                row[name] = check.numbers(rec, ref)
        per_seed[str(seed)] = row
        harness.log(f"seed {seed} ({time.perf_counter() - t0:.1f} s): "
                    f"{json.dumps(harness.finite(row))}")
    summary = {}
    for who in ("program", "control", "half_batch", "state_unchanged", "draw_altered",
                "plan_altered"):
        rows = [r[who] for r in per_seed.values() if who in r]
        if rows:
            pick = max if who == "program" else min
            summary[who] = {k: pick(r[k] for r in rows) for k in rows[0]}
    return {"workload": cell_name, "seeds": per_seed, "summary": summary}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--control-seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--out", help="also write the readings to this JSON file")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.log(f"device: {json.dumps(harness.device_info())}")
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",")}
    seeds += sorted(control - set(seeds))
    out = harness.finite(readings(harness.Bench(), args.workload, seeds, control))
    text = json.dumps(out, allow_nan=False)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(json.dumps(out["summary"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
