"""Multi-device numerical tests of the LM substrate (subprocess: 8 host
devices).

The dry-run proves the sharded programs COMPILE; these prove elastic
resharding and the sharded train step produce the right NUMBERS. They
run in a subprocess because the device count must be fixed before jax
initializes (the main test process keeps the default single device, per
the harness contract). The reconstruction fleet's multi-device tests are
in test_fleet.py.
"""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh

out = {}

# ---- elastic resharding roundtrip ----------------------------------------
from repro.launch import sharding as shd
from repro.runtime import reshard_tree

tree = {"layers": {"mlp": {"wi_gate": jnp.arange(4 * 8 * 16,
                                                 dtype=jnp.float32
                                                 ).reshape(4, 8, 16)}}}
mesh_a = make_mesh((4, 2), ("data", "model"))
mesh_b = make_mesh((2, 4), ("data", "model"))

def spec_fn_for(mesh):
    return lambda path, leaf: shd.spec_for_param(path, leaf.shape, mesh)

t_a = reshard_tree(tree, mesh_a, spec_fn_for(mesh_a))
t_b = reshard_tree(t_a, mesh_b, spec_fn_for(mesh_b))
same = bool(jnp.array_equal(t_b["layers"]["mlp"]["wi_gate"],
                            tree["layers"]["mlp"]["wi_gate"]))
out["reshard_roundtrip_equal"] = same
out["reshard_b_sharded"] = str(
    t_b["layers"]["mlp"]["wi_gate"].sharding.spec)

# ---- sharded train step == single-device step ----------------------------
from repro.configs import RunConfig, ShapeConfig, get_smoke_config
from repro.launch.train import (TrainState, init_state, make_train_step,
                                shard_train_step)
from repro.models import build_model

cfg = get_smoke_config("qwen2.5-3b")
model = build_model(cfg)
state = init_state(model, RunConfig(seed=0))
batch = model.dummy_batch(ShapeConfig("t", "train", 16, 4))
step = make_train_step(model, RunConfig(), total_steps=100)
(_, m_single) = jax.jit(step)(state, batch)

mesh2 = make_mesh((4, 2), ("data", "model"))
aparams = jax.eval_shape(lambda: model.init(0))
jit_step, state_sh = shard_train_step(step, model, mesh2, aparams, batch)
(_, m_sharded) = jit_step(state, batch)
out["train_loss_single"] = float(m_single["loss"])
out["train_loss_sharded"] = float(m_sharded["loss"])

print("RESULT:" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def multidevice_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT],
                          capture_output=True, text=True, timeout=900,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))), env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT:")][-1]
    return json.loads(line[len("RESULT:"):])


def test_elastic_reshard_roundtrip(multidevice_results):
    assert multidevice_results["reshard_roundtrip_equal"]
    assert "model" in multidevice_results["reshard_b_sharded"]


def test_sharded_train_step_matches_single(multidevice_results):
    a = multidevice_results["train_loss_single"]
    b = multidevice_results["train_loss_sharded"]
    assert abs(a - b) / abs(a) < 1e-4, (a, b)
