"""Small-mesh lowering tests: the dry-run machinery on 8 fake CPU devices.

The 512-device flag must not leak into the other tests, so these run in a
subprocess with their own XLA_FLAGS.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, dataclasses
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.launch.mesh import make_host_mesh, batch_axes
from repro.launch.sharding import param_shardings, batch_shardings, cache_shardings
from repro.launch.steps import abstract_params, make_step, default_optimizer, input_specs
from repro.launch.dryrun import build_shardings
from repro.launch import roofline as rl
from repro.models.config import InputShape
from repro.models.sharding_hints import sharding_hints

results = {}
mesh = make_host_mesh(2, 4)
for arch, kind in [("qwen3-0.6b", "train"), ("xlstm-125m", "decode"), ("deepseek-v2-lite-16b", "train")]:
    cfg = get_config(arch, reduced=True)
    cfg = dataclasses.replace(cfg, d_model=256, vocab_size=1024, scan_layers=False)
    shape = InputShape("t", 64, 8, kind)
    opt = default_optimizer()
    step_fn, k2 = make_step(cfg, shape, opt)
    in_sh, out_sh, (state_shape, specs) = build_shardings(cfg, shape, mesh, k2, opt)
    with mesh, sharding_hints(batch_axes(mesh)):
        jitted = jax.jit(step_fn, in_shardings=in_sh, out_shardings=out_sh)
        compiled = jitted.lower(state_shape, specs).compile()
    cost = compiled.cost_analysis()
    colls = rl.parse_collectives(compiled.as_text())
    results[arch] = {
        "flops": cost.get("flops", 0.0),
        "collective_bytes": sum(v["bytes"] for v in colls.values()),
        "mem_args": compiled.memory_analysis().argument_size_in_bytes,
    }

# FL engine: the batched round with the client axis sharded over "data",
# image-shaped clients, lowered from the same launch-layer hooks
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.steps import fl_engine_input_specs, fl_engine_shardings, make_fl_engine_step
from repro.models.simple import classification_loss, init_mlp
from repro.optim import sgd


def image_loss(params, x, y):
    return classification_loss(params, x.reshape(x.shape[0], -1), y)


specs = fl_engine_input_specs(
    n_clients=8, m_slots=4, n_pad=16, feat_shape=(4, 4), n_steps=2, batch_size=8
)
sh = fl_engine_shardings(mesh, specs)
fl_params = init_mlp((16, 32, 10), seed=0)
p_repl = jax.tree_util.tree_map(lambda l: NamedSharding(mesh, P()), fl_params)
p_abs = jax.tree_util.tree_map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), fl_params)
step = make_fl_engine_step(image_loss, sgd(0.1), mesh=mesh)
with mesh:
    compiled = jax.jit(step, in_shardings=(p_repl, sh)).lower(p_abs, specs).compile()
cost = compiled.cost_analysis()
colls = rl.parse_collectives(compiled.as_text())
results["fl_engine"] = {
    "flops": cost.get("flops", 0.0),
    "collective_bytes": sum(v["bytes"] for v in colls.values()),
    "mem_args": compiled.memory_analysis().argument_size_in_bytes,
}
print(json.dumps(results))
"""


@pytest.fixture(scope="module")
def lowering_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True, env=env, cwd=ROOT,
        timeout=560,
    )
    assert out.returncode == 0, f"lowering subprocess failed:\n{out.stderr[-3000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_reduced_configs_lower_on_2x4_mesh(lowering_results):
    assert set(lowering_results) == {
        "qwen3-0.6b", "xlstm-125m", "deepseek-v2-lite-16b", "fl_engine",
    }
    for arch, rec in lowering_results.items():
        assert rec["flops"] > 0, arch
        assert rec["mem_args"] > 0, arch


def test_fl_engine_lowers_sharded_with_one_collective_round(lowering_results):
    """The batched FL round lowers with the client axis sharded over "data";
    the weighted aggregation forces real cross-client communication."""
    assert lowering_results["fl_engine"]["collective_bytes"] > 0


def test_train_steps_emit_collectives(lowering_results):
    # sharded training must communicate (grad reduction at minimum)
    assert lowering_results["qwen3-0.6b"]["collective_bytes"] > 0
    assert lowering_results["deepseek-v2-lite-16b"]["collective_bytes"] > 0


def test_collective_parser_on_synthetic_hlo():
    from repro.launch.roofline import parse_collectives

    hlo = """
  %all-gather.1 = bf16[8,128]{1,0} all-gather(%x), replica_groups=[2,4]<=[8]
  %ar = (f32[16], f32[4,4]) all-reduce(%a, %b), to_apply=%sum
  %done = f32[8] all-reduce-done(%start)
  %unrelated = f32[4] add(%p, %q)
  %a2a = f32[2,64]{1,0} all-to-all(%y), dimensions={0}
"""
    d = parse_collectives(hlo)
    assert d["all-gather"]["count"] == 1
    assert d["all-gather"]["bytes"] == 8 * 128 * 2
    assert d["all-reduce"]["count"] == 1  # -done must NOT double count
    assert d["all-reduce"]["bytes"] == 16 * 4 + 16 * 4
    assert d["all-to-all"]["bytes"] == 2 * 64 * 4


def test_roofline_terms_and_dominance():
    from repro.launch.roofline import Roofline

    r = Roofline(
        arch="a", shape="s", mesh="16x16", chips=256,
        flops_per_chip=197e12, bytes_per_chip=819e9 / 2, coll_bytes_per_chip=50e9 * 2,
        coll_detail={}, model_flops_global=197e12 * 256 / 2,
    )
    assert abs(r.t_compute - 1.0) < 1e-9
    assert abs(r.t_memory - 0.5) < 1e-9
    assert abs(r.t_collective - 2.0) < 1e-9
    assert r.dominant == "collective"
    assert abs(r.utility_ratio - 0.5) < 1e-9
