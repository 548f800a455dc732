"""Sharding rules + small-mesh distributed execution (subprocess: the
device-count flag must be set before jax init, so multi-device tests run in
their own interpreter)."""
import json
import subprocess
import sys

import pytest

from jax.sharding import PartitionSpec as P


def test_spec_from_axes_divisibility():
    from repro.sharding import rules

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 8}

    r = rules.rules_for("train")
    # heads=16 divisible by 8 -> model; embed -> data
    spec = rules.spec_from_axes(("embed", "heads", "head_dim"),
                                (64, 16, 128), r, FakeMesh())
    assert spec == P("data", "model", None)
    # heads=6 NOT divisible -> falls to head_dim
    spec = rules.spec_from_axes(("embed", "heads", "head_dim"),
                                (64, 6, 128), r, FakeMesh())
    assert spec == P("data", None, "model")
    # serve mode: no fsdp on embed
    r2 = rules.rules_for("serve")
    spec = rules.spec_from_axes(("embed", "ff"), (64, 128), r2, FakeMesh())
    assert spec == P(None, "model")


_DISTRIBUTED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import InputShape
from repro.configs.registry import reduced_config
from repro.launch.dryrun import build
from repro.launch.mesh import make_test_mesh
from repro.models import transformer
from repro.optim import adamw
from repro.runtime import steps
from repro.sharding import rules

cfg = reduced_config("qwen3-1.7b")
mesh = make_test_mesh(data=4, model=2)
shape = InputShape("tiny_train", seq_len=32, global_batch=8, kind="train")

fn, args, in_sh = build(cfg, shape, mesh)
with jax.set_mesh(mesh):
    lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
    compiled = lowered.compile()

# now ACTUALLY run the distributed step with real arrays and compare with
# the single-device result
params = transformer.init_params(cfg, jax.random.PRNGKey(0))
opt = adamw.init(params)
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)
batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}

step = steps.train_step(cfg, adamw.AdamWConfig())
with jax.set_mesh(mesh):
    p_sh, o_sh, b_sh = in_sh
    params_d = jax.device_put(params, p_sh)
    opt_d = jax.device_put(opt, o_sh)
    batch_d = jax.device_put(batch, b_sh)
    _, _, metrics_d = jax.jit(step, in_shardings=in_sh)(params_d, opt_d, batch_d)
_, _, metrics_1 = step(params, opt, batch)
out = {
    "loss_distributed": float(metrics_d["loss"]),
    "loss_single": float(metrics_1["loss"]),
    "compiled_ok": True,
}
print("RESULT::" + json.dumps(out))
"""


@pytest.mark.slow
def test_distributed_train_step_matches_single_device():
    proc = subprocess.run(
        [sys.executable, "-c", _DISTRIBUTED_SCRIPT],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT::")][0]
    out = json.loads(line[len("RESULT::"):])
    assert out["compiled_ok"]
    assert abs(out["loss_distributed"] - out["loss_single"]) < 1e-2, out


_DECODE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp

from repro.configs.base import InputShape
from repro.configs.registry import reduced_config
from repro.launch.dryrun import build
from repro.launch.mesh import make_test_mesh

ok = {}
for arch in ("qwen3-1.7b", "zamba2-2.7b", "qwen2-moe-a2.7b"):
    cfg = reduced_config(arch)
    mesh = make_test_mesh(data=2, model=2, pod=2)
    shape = InputShape("tiny_decode", seq_len=64, global_batch=4, kind="decode")
    fn, args, in_sh = build(cfg, shape, mesh)
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
    ok[arch] = True
print("RESULT::" + json.dumps(ok))
"""


@pytest.mark.slow
def test_multipod_decode_lowers():
    proc = subprocess.run(
        [sys.executable, "-c", _DECODE_SCRIPT],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT::")][0]
    out = json.loads(line[len("RESULT::"):])
    assert all(out.values()) and len(out) == 3
