import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Dry-run for the PAPER'S round step: clustered-sampling FL at pod scale.

Lowers ``fl_round_step`` (m clients × N unsynchronized local steps ×
weighted parameter combine) on the production mesh and records the same
cost/collective analysis as the synchronous ``train_step`` dry-run — the
head-to-head that quantifies the paper's communication claim on TPU
collectives (EXPERIMENTS.md §Perf).

Usage:
  python -m repro.launch.dryrun_fl --arch qwen3-0.6b --local-steps 8
"""
import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_NAMES, get_config
from repro.launch import roofline as rl
from repro.launch.fl_train import fl_input_specs, fl_round_shardings, make_fl_round_step
from repro.launch.mesh import data_parallel_degree, make_production_mesh, mesh_chips
from repro.launch.sharding import param_shardings, replicated
from repro.launch.steps import abstract_params
from repro.models.config import INPUT_SHAPES
from repro.models.sharding_hints import sharding_hints


def run_fl_round(
    arch: str,
    *,
    n_local: int,
    multi_pod: bool = False,
    seq_len: int = 4096,
    global_batch: int = 256,
    out_dir: str = "experiments/dryrun",
    variants: list[str] | None = None,
    planner: str = "none",
):
    from repro.launch.dryrun import apply_variants  # shares variant plumbing

    if planner not in ("none", "sync", "async"):
        raise ValueError(f"unknown planner {planner!r}; choose none | sync | async")
    t0 = time.time()
    cfg = apply_variants(get_config(arch), variants or [])
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_chips(mesh)
    m = data_parallel_degree(mesh)  # one client per data group
    local_batch = global_batch // m

    # with a planner, the round also emits the (m, d) flat representative
    # gradients that feed Algorithm 2's device-resident store — lower that
    # variant so its extra output (and shardings) are part of the analysis
    with_updates = planner != "none"
    step_fn = make_fl_round_step(
        cfg, lr=1e-2, n_local_steps=n_local, with_updates=with_updates
    )
    specs = fl_input_specs(cfg, m, n_local, local_batch, seq_len)

    # cross-silo layout: params replicated over the client/data axes
    # (each client trains its own copy), tensor-parallel over "model"
    p_sh = param_shardings(mesh, abstract_params(cfg))
    p_repl = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, P(*[e if e == "model" else None for e in s.spec])),
        p_sh,
    )
    batch_sh = fl_round_shardings(mesh)
    loss_sh = replicated(mesh, jax.eval_shape(lambda: jnp.zeros(())))

    # NOTE: the in-model sequence-parallel constraints (sharding_hints) are
    # NOT active here — combining them with the vmapped client axis trips an
    # XLA SPMD partitioner CHECK (device-group mismatch, observed with jax
    # 0.8.2). Attention TP inside a client therefore relies on GSPMD
    # propagation only; the quantity under study — the *client-axis*
    # collective schedule (per-round weighted combine vs per-step gradient
    # all-reduce) — is unaffected.
    from repro.launch.mesh import leading_batch_spec

    d_model_flat = sum(
        int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(abstract_params(cfg))
    )
    # planner feed: flat updates sharded over the client axis like the batch
    upd_sh = NamedSharding(mesh, leading_batch_spec(mesh, 2))
    out_sh = (p_repl, loss_sh, upd_sh) if with_updates else (p_repl, loss_sh)
    with mesh:
        jitted = jax.jit(
            lambda p, b: step_fn(p, b["client_tokens"], b["client_targets"], b["weights"]),
            in_shardings=(p_repl, batch_sh),
            out_shardings=out_sh,
        )
        compiled = jitted.lower(abstract_params(cfg), specs).compile()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    colls = rl.parse_collectives(compiled.as_text())
    # NOTE: the model body runs under vmap+scan(local steps) — while-loop
    # body counted once, so per-LOCAL-STEP cost ≈ reported cost directly;
    # the collective combine happens ONCE per round (outside the scan) and
    # is correctly counted once.
    total_coll = sum(v["bytes"] for v in colls.values())
    rec = {
        "arch": arch,
        "shape": f"fl_round_N{n_local}",
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "kind": "fl_round",
        "m_clients": m,
        "n_local_steps": n_local,
        "flops_per_chip_per_local_step": float(cost.get("flops", 0.0)),
        "coll_bytes_per_chip_per_round": float(total_coll),
        "coll_bytes_per_chip_per_step": float(total_coll) / n_local,
        "coll_detail": colls,
        "t_collective_per_step": float(total_coll) / n_local / rl.LINK_BW,
        "hbm_per_chip_gb": round(
            (mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes)
            / 2**30, 3,
        ),
        # async keeps the rebuild off the round's critical path entirely; the
        # device-side cost of feeding it is the (m, d) f32 updates output
        "planner": planner,
        "planner_feed_bytes": (m * d_model_flat * 4) if with_updates else 0,
        "variants": variants or [],
        "compile_s": round(time.time() - t0, 1),
    }
    os.makedirs(out_dir, exist_ok=True)
    tag = "+".join(variants or []) or "baseline"
    if planner != "none":
        tag += f"+planner-{planner}"
    with open(
        os.path.join(out_dir, f"{arch}__fl_round_N{n_local}__{rec['mesh']}__{tag}.json"), "w"
    ) as f:
        json.dump(rec, f, indent=1)
    print(
        f"[OK] {arch} fl_round N={n_local} mesh={rec['mesh']} "
        f"coll/round={total_coll / 2**20:.1f}MiB coll/step={total_coll / n_local / 2**20:.1f}MiB "
        f"tx/step={rec['t_collective_per_step'] * 1e3:.2f}ms hbm={rec['hbm_per_chip_gb']}GB "
        f"({rec['compile_s']}s)",
        flush=True,
    )
    return rec


def planner_from_spec(spec_arg: str) -> str:
    """Derive the planner variant to lower from an experiment-spec JSON.

    ``spec_arg`` is inline JSON or a path to a JSON file with (at least)
    ``sampler`` / ``planner`` sections (``repro.fl.experiment`` schema). A
    sampler that consumes representative gradients lowers the planner-fed
    round in the spec's planner mode; plan-free samplers lower the plain
    round (``"none"``).
    """
    from repro.core.samplers import SAMPLERS
    from repro.fl.experiment import PlannerSpec, SamplerSpec, load_spec_dict

    d = load_spec_dict(spec_arg)
    sampler = SamplerSpec.from_dict(d.get("sampler", {"name": "algorithm2", "m": 1}))
    planner = PlannerSpec.from_dict(d.get("planner", {}))
    consumes = getattr(SAMPLERS.get(sampler.name), "consumes_updates", False)
    return planner.mode if consumes else "none"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="qwen3-0.6b")
    ap.add_argument("--local-steps", type=int, default=8)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument(
        "--planner", choices=("none", "sync", "async"), default="none",
        help="lower the planner-fed round variant (emits the (m, d) flat "
        "representative gradients Algorithm 2's gradient store consumes)",
    )
    ap.add_argument(
        "--spec", default=None,
        help="experiment-spec JSON (inline or a file path); its sampler/"
        "planner sections pick the round variant to lower (overrides --planner)",
    )
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    planner = planner_from_spec(args.spec) if args.spec else args.planner
    run_fl_round(
        args.arch, n_local=args.local_steps, multi_pod=args.multi_pod,
        out_dir=args.out, planner=planner,
    )


if __name__ == "__main__":
    main()
