"""Mini dry-run on a small host-device mesh, in a subprocess (the device-count
flag must be set before jax initializes — never in this test process)."""
import json
import os
import subprocess
import sys

import pytest

MINI = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, functools
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs as C
from repro.launch import specs as S
from repro.launch.mesh import make_test_mesh
from repro.launch.dryrun import collective_stats
from repro.models import decode_step, prefill
from repro.training.optimizer import OptimizerConfig
from repro.training.train_step import train_step

results = {}
for arch in ["phi3-mini-3.8b", "kimi-k2-1t-a32b", "mamba2-780m"]:
    cfg = C.smoke_config(arch).with_overrides(grad_accum=2)
    mesh = make_test_mesh(data=2, model=2, pod=2)   # 2x2x2 = 8 "chips"
    with jax.set_mesh(mesh):
        oc = OptimizerConfig()
        p_structs = S.param_structs(cfg)
        p_shard = S.param_shardings(cfg, mesh, p_structs)
        o_structs = S.opt_structs(cfg, oc)
        o_shard = S.opt_shardings(cfg, oc, mesh, o_structs=o_structs)
        b_structs = S.batch_structs(cfg, 8, 32, train=True)
        b_shard = S.batch_shardings(mesh, b_structs)
        fn = functools.partial(train_step, cfg=cfg, oc=oc)
        lowered = jax.jit(fn, in_shardings=(p_shard, o_shard, b_shard)).lower(
            p_structs, o_structs, b_structs)
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        coll = collective_stats(compiled.as_text())
        results[arch] = {
            "flops": cost.get("flops", 0.0),
            "collective_bytes": coll["total_bytes"],
            "mem": compiled.memory_analysis().temp_size_in_bytes,
        }
print("RESULTS_JSON:" + json.dumps(results))
"""


@pytest.mark.slow
def test_mini_multipod_dryrun():
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run([sys.executable, "-c", MINI], capture_output=True,
                          text=True, env=env, cwd=os.path.dirname(
                              os.path.dirname(os.path.abspath(__file__))),
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULTS_JSON:")][0]
    results = json.loads(line.split(":", 1)[1])
    for arch, r in results.items():
        assert r["flops"] > 0, f"{arch}: no flops recorded"
    # data-parallel grads must all-reduce -> nonzero collective traffic
    assert results["phi3-mini-3.8b"]["collective_bytes"] > 0


def test_collective_parser():
    from repro.launch.dryrun import collective_stats

    hlo = """
  %ag = f32[16,128]{1,0} all-gather(%x), dimensions={0}
  %ar = (bf16[8,8]{1,0}, bf16[8,8]{1,0}) all-reduce(%a, %b), to_apply=%add
  %rs = f32[4,64]{1,0} reduce-scatter(%y), dimensions={0}
  %cp = s8[128]{0} collective-permute(%z)
  %nothing = f32[2,2]{1,0} add(%p, %q)
"""
    st = collective_stats(hlo)
    assert st["counts"]["all-gather"] == 1
    assert st["bytes_by_op"]["all-gather"] == 16 * 128 * 4
    assert st["bytes_by_op"]["all-reduce"] == 2 * 8 * 8 * 2
    assert st["bytes_by_op"]["reduce-scatter"] == 4 * 64 * 4
    assert st["bytes_by_op"]["collective-permute"] == 128
    assert st["total_bytes"] == sum(st["bytes_by_op"].values())
