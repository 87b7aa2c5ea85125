import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) combination.

The two lines above MUST stay the first statements of this module — jax
locks the device count on first initialization, and the production meshes
need 512 placeholder host devices (2 pods × 16 × 16).

Per combination this script:
  1. builds the step function (train_step / prefill / serve_step),
  2. jits it with the sharding rules of ``repro.launch.sharding``,
  3. ``.lower(**input_specs).compile()`` against ShapeDtypeStructs
     (no allocation),
  4. records ``memory_analysis()`` (fits-per-chip proof),
     ``cost_analysis()`` (FLOPs / bytes) and the parsed collective
     schedule into experiments/dryrun/*.json for §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  python -m repro.launch.dryrun --all                    # 40 baselines
  python -m repro.launch.dryrun --all --multi-pod        # 512-chip pass
  python -m repro.launch.dryrun ... --variant fused_ce --variant absorbed_mla
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro.configs import ARCH_NAMES, get_config
from repro.launch import roofline as rl
from repro.launch.mesh import batch_axes, make_production_mesh, mesh_chips
from repro.models.sharding_hints import sharding_hints
from repro.launch.sharding import (
    batch_shardings,
    cache_shardings,
    opt_state_shardings,
    param_shardings,
    replicated,
)
from repro.launch.steps import (
    abstract_params,
    abstract_train_state,
    default_optimizer,
    input_specs,
    make_step,
)
from repro.models import model as mdl
from repro.models.config import INPUT_SHAPES

VARIANTS = (
    "fused_ce",
    "absorbed_mla",
    "block_attn",
    "expert_parallel",
    "no_remat",
    "mlstm_chunk",
    "sp_residual",
)


def apply_variants(cfg, variants: list[str]):
    if "fused_ce" in variants:
        cfg = dataclasses.replace(cfg, fused_ce=True)
    if "absorbed_mla" in variants and cfg.mla is not None:
        cfg = dataclasses.replace(cfg, mla=dataclasses.replace(cfg.mla, decode_mode="absorbed"))
    if "block_attn" in variants:
        cfg = dataclasses.replace(cfg, attn_block_q=512)
    if "no_remat" in variants:
        cfg = dataclasses.replace(cfg, remat=False)
    if "mlstm_chunk" in variants:
        cfg = dataclasses.replace(cfg, mlstm_chunk=2048)
    if "sp_residual" in variants:
        cfg = dataclasses.replace(cfg, seq_parallel_residual=True)
    return cfg


def build_shardings(cfg, shape, mesh, step_kind, opt, *, expert_parallel=False):
    """(in_shardings tuple, out_shardings) for the jitted step."""
    specs = input_specs(cfg, shape)
    p_sh = param_shardings(mesh, abstract_params(cfg), expert_parallel=expert_parallel)

    if step_kind == "train":
        state_shape = abstract_train_state(cfg, opt)
        state_sh = {
            "params": p_sh,
            "opt_state": opt_state_shardings(mesh, state_shape["opt_state"], p_sh),
            "step": replicated(mesh, state_shape["step"]),
        }
        batch_sh = batch_shardings(mesh, specs)
        metrics_sh = replicated(
            mesh,
            jax.eval_shape(
                lambda: {
                    "loss": jax.numpy.zeros(()),
                    "grad_norm": jax.numpy.zeros(()),
                    "ce": jax.numpy.zeros(()),
                    "aux": jax.numpy.zeros(()),
                }
            ),
        )
        return (state_sh, batch_sh), (state_sh, metrics_sh), (state_shape, specs)

    # prefill / decode
    batch_sh = {}
    for k, v in specs.items():
        if k == "caches":
            batch_sh[k] = cache_shardings(mesh, v, cfg)
        else:
            batch_sh[k] = batch_shardings(mesh, {k: v})[k]
    params_shape = abstract_params(cfg)
    if step_kind == "prefill":
        # outputs: (last logits, caches)
        cache_shape = jax.eval_shape(
            lambda: mdl.init_cache(
                cfg, shape.global_batch, shape.seq_len, jax.numpy.dtype(cfg.dtype)
            )
        )
        logits_sh = batch_shardings(
            mesh,
            jax.ShapeDtypeStruct((shape.global_batch, cfg.vocab_size), jax.numpy.dtype(cfg.dtype)),
        )
        out_sh = (logits_sh, cache_shardings(mesh, cache_shape, cfg))
    else:
        logits_sh = batch_shardings(
            mesh,
            jax.ShapeDtypeStruct((shape.global_batch, cfg.vocab_size), jax.numpy.dtype(cfg.dtype)),
        )
        out_sh = (logits_sh, batch_sh["caches"])
    return (p_sh, batch_sh), out_sh, (params_shape, specs)


def _with_repeats(cfg, n: int):
    """A structurally-identical config with ``n`` pattern repeats (and a
    matching encoder depth for enc-dec archs)."""
    n_layers = len(cfg.first_blocks) + len(cfg.pattern) * n + len(cfg.tail_blocks)
    enc = cfg.encoder
    if enc is not None:
        enc = dataclasses.replace(enc, n_layers=n)
    return dataclasses.replace(cfg, n_layers=n_layers, encoder=enc, scan_layers=False)


def _compile(cfg, shape, mesh, *, expert_parallel: bool):
    opt = default_optimizer()
    step_fn, kind = make_step(cfg, shape, opt)
    in_sh, out_sh, (state_shape, specs) = build_shardings(
        cfg, shape, mesh, kind, opt, expert_parallel=expert_parallel
    )
    with mesh, sharding_hints(batch_axes(mesh)):
        jitted = jax.jit(
            step_fn,
            in_shardings=in_sh,
            out_shardings=out_sh,
            donate_argnums=0 if kind == "train" else (),
        )
        compiled = jitted.lower(state_shape, specs).compile()
    return compiled, kind, state_shape


def _costs(compiled):
    cost = compiled.cost_analysis()
    colls = rl.parse_collectives(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "colls": colls,
    }


def _slstm_correction_flops(cfg, shape, kind: str, chips: int) -> float:
    """sLSTM time-steps run inside a lax.scan whose body XLA counts once;
    add the analytically-exact recurrent matmul flops for the missing
    S-1 steps (4 gates × per-head hd² matmuls). Global flops / chips."""
    n_slstm = sum(1 for mx, _ in cfg.all_blocks if mx == "slstm")
    if n_slstm == 0 or kind == "decode":
        return 0.0
    from repro.models.layers.xlstm import _slstm_dims

    d_in, hd = _slstm_dims(cfg)
    steps = shape.seq_len - 1  # body counted once already
    per_step = 4 * cfg.n_heads * hd * hd * 2 * shape.global_batch
    mult = 3.0 if kind == "train" else 1.0  # fwd + bwd(2x)
    return n_slstm * steps * per_step * mult / chips


def run_one(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool,
    variants: list[str],
    out_dir: str,
    lower_only: bool = False,
):
    t0 = time.time()
    shape = INPUT_SHAPES[shape_name]
    cfg = apply_variants(get_config(arch), variants)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_chips(mesh)
    ep = "expert_parallel" in variants

    # (a) production artifact: full depth, scan-over-layers — proves the
    # (arch × shape × mesh) combination lowers+compiles; memory analysis.
    compiled, kind, state_shape = _compile(cfg, shape, mesh, expert_parallel=ep)
    mem = compiled.memory_analysis()

    if lower_only:
        # multi-pod pass: the lowering + memory proof only (roofline terms
        # are reported on the single-pod mesh per the brief)
        c1 = c2 = _costs(compiled)
        r_full = 1
    else:
        # (b) cost accounting: XLA counts while-loop bodies once, so flops /
        # bytes / collectives come from two small UNROLLED compiles (1 and 2
        # pattern repeats) extrapolated linearly — exact for homogeneous stacks.
        r_full = cfg.n_repeats
        c1 = _costs(_compile(_with_repeats(cfg, 1), shape, mesh, expert_parallel=ep)[0])
        c2 = (
            _costs(_compile(_with_repeats(cfg, 2), shape, mesh, expert_parallel=ep)[0])
            if r_full > 1
            else c1
        )

    def extrap(f1: float, f2: float) -> float:
        return f1 + (r_full - 1) * (f2 - f1)

    flops = extrap(c1["flops"], c2["flops"]) + _slstm_correction_flops(cfg, shape, kind, chips)
    bytes_ = extrap(c1["bytes"], c2["bytes"])
    colls = {
        k: {
            "count": int(extrap(c1["colls"][k]["count"], c2["colls"][k]["count"])),
            "bytes": extrap(c1["colls"][k]["bytes"], c2["colls"][k]["bytes"]),
        }
        for k in c1["colls"]
    }
    cost = {"flops": flops, "bytes accessed": bytes_}
    total_coll = sum(v["bytes"] for v in colls.values())

    params_shape = state_shape["params"] if kind == "train" else state_shape
    n_total, n_active = rl.active_params(params_shape, cfg)
    tokens = shape.tokens if kind != "decode" else shape.global_batch  # 1 new token each
    mf = rl.model_flops(n_active, tokens, kind)

    roof = rl.Roofline(
        arch=arch,
        shape=shape_name,
        mesh="2x16x16" if multi_pod else "16x16",
        chips=chips,
        flops_per_chip=float(cost.get("flops", 0.0)),
        bytes_per_chip=float(cost.get("bytes accessed", 0.0)),
        coll_bytes_per_chip=float(total_coll),
        coll_detail=colls,
        model_flops_global=mf,
        arg_bytes_per_chip=mem.argument_size_in_bytes,
        temp_bytes_per_chip=mem.temp_size_in_bytes,
        out_bytes_per_chip=mem.output_size_in_bytes,
    )
    rec = roof.to_dict()
    rec.update(
        n_params=n_total,
        n_params_active=n_active,
        variants=variants,
        kind=kind,
        lower_only=lower_only,
        compile_s=round(time.time() - t0, 1),
        hbm_per_chip_gb=round(
            (mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes)
            / 2**30, 3,
        ),
    )
    os.makedirs(out_dir, exist_ok=True)
    tag = "+".join(variants) if variants else "baseline"
    fname = f"{arch}__{shape_name}__{rec['mesh']}__{tag}.json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(rec, f, indent=1)

    print(
        f"[OK] {arch:22s} {shape_name:12s} mesh={rec['mesh']:8s} {tag:14s} "
        f"args={mem.argument_size_in_bytes/2**30:6.2f}GiB temp={mem.temp_size_in_bytes/2**30:7.2f}GiB "
        f"flops/chip={rec['flops_per_chip']:.3e} coll/chip={total_coll/2**20:9.1f}MiB "
        f"tc={roof.t_compute*1e3:8.2f}ms tm={roof.t_memory*1e3:8.2f}ms "
        f"tx={roof.t_collective*1e3:8.2f}ms dom={roof.dominant:10s} "
        f"util={roof.utility_ratio:5.2f} ({rec['compile_s']}s)",
        flush=True,
    )
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=tuple(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true", help="all (arch × shape) baselines")
    ap.add_argument("--variant", action="append", default=[], choices=VARIANTS)
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument(
        "--lower-only",
        action="store_true",
        help="skip the cost-accounting compiles (multi-pod lowering pass)",
    )
    args = ap.parse_args()

    combos = (
        [(a, s) for a in ARCH_NAMES for s in INPUT_SHAPES]
        if args.all
        else [(args.arch, args.shape)]
    )
    failures = []
    for arch, shape in combos:
        try:
            run_one(
                arch,
                shape,
                multi_pod=args.multi_pod,
                variants=args.variant,
                out_dir=args.out,
                lower_only=args.lower_only,
            )
        except Exception as e:  # noqa: BLE001 - report and continue the matrix
            failures.append((arch, shape, repr(e)))
            print(f"[FAIL] {arch} {shape}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures: {failures}")
    print("dry-run complete: all combinations lowered and compiled.")


if __name__ == "__main__":
    main()
