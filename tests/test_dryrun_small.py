"""Mesh integration tests in subprocesses (XLA device count must be set
before jax initializes, so these run out-of-process on an 8-device CPU
mesh with reduced configs). Validates the full launch path: shardings,
DRACO window step, gossip lowering (dense + ring), unification."""
import os
import subprocess
import sys
import textwrap

import pytest

# tier-2: mesh dry-run subprocess battery (ROADMAP tier-1 runs
# -m "not slow")
pytestmark = pytest.mark.slow

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, timeout=900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


PRELUDE = """
import jax, jax.numpy as jnp
import numpy as np
from repro.configs.base import get_reduced, ShapeConfig
from repro.launch import steps as steps_lib, mesh as mesh_lib
from repro.core.topology import adjacency, row_stochastic
import repro.models.model as M
mesh = mesh_lib.make_test_mesh((4, 2))
assert len(jax.devices()) == 8
"""


def test_train_step_executes_on_mesh():
    out = _run(PRELUDE + """
cfg = get_reduced("qwen2-1.5b")
shape = ShapeConfig("t", 32, 8, "train")
step = steps_lib.make_train_step(cfg, mesh, lr=1e-2, mix_mode="dense")
param_sh, batch_sh, q_sh = steps_lib.make_shardings(mesh, cfg, shape)
key = jax.random.PRNGKey(0)
p0 = M.init_params(key, cfg)
params = jax.tree_util.tree_map(lambda p: jnp.broadcast_to(p[None], (4,) + p.shape), p0)
params = jax.device_put(params, param_sh)
batch = {"tokens": jax.device_put(
    jax.random.randint(key, (4, 2, 32), 0, cfg.vocab_size), batch_sh["tokens"])}
q = jax.device_put(row_stochastic(adjacency("cycle", 4)), q_sh)
jitted = jax.jit(step, in_shardings=(param_sh, batch_sh, q_sh),
                 out_shardings=(param_sh, None))
new_params, loss = jitted(params, batch, q)
assert np.isfinite(float(loss)), loss
changed = any(float(jnp.abs(a - b).max()) > 0 for a, b in zip(
    jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(new_params)))
assert changed
print("TRAIN_STEP_OK", float(loss))
""")
    assert "TRAIN_STEP_OK" in out


def test_ring_mix_equals_dense_cycle():
    """collective_permute ring gossip == dense einsum with cycle Q."""
    out = _run(PRELUDE + """
from repro.core import mixing
n = 4
deltas = {"w": jax.random.normal(jax.random.PRNGKey(1), (n, 16))}
from jax.sharding import NamedSharding, PartitionSpec as P
sh = NamedSharding(mesh, P("data", None))
deltas = jax.device_put(deltas, {"w": sh})
q = row_stochastic(adjacency("cycle", n))  # 0.5 each neighbor
dense = mixing.mix_dense(q, deltas)
ring = jax.jit(lambda d: mixing.mix_ring_shardmap(mesh, ("data",), d))(deltas)
np.testing.assert_allclose(np.asarray(dense["w"]), np.asarray(ring["w"]),
                           atol=1e-5, rtol=1e-5)
print("RING_OK")
""")
    assert "RING_OK" in out


def test_unify_step_on_mesh():
    out = _run(PRELUDE + """
cfg = get_reduced("stablelm-3b")
shape = ShapeConfig("t", 32, 8, "train")
param_sh, _, _ = steps_lib.make_shardings(mesh, cfg, shape)
key = jax.random.PRNGKey(0)
params = jax.vmap(lambda k: M.init_params(k, cfg))(jax.random.split(key, 4))
params = jax.device_put(params, param_sh)
unify = jax.jit(steps_lib.make_unify_step(cfg, mesh))
out_p = unify(params, jnp.asarray(2, jnp.int32))
for leaf in jax.tree_util.tree_leaves(out_p):
    assert float(jnp.abs(leaf - leaf[0:1]).max()) == 0.0
print("UNIFY_OK")
""")
    assert "UNIFY_OK" in out
