"""Distributed behaviour under 8 stub devices (subprocess: jax locks the
device count at first init, so each scenario runs in its own process)."""
import json
import os
import subprocess
import sys

import pytest

# Subprocess compiles on stub device meshes: minutes each on a CPU
# runner. Nightly / 'run-slow'-labeled tier only.
pytestmark = pytest.mark.slow

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SRC = os.path.join(ROOT, "src")


def _run(code: str, devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=ROOT,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_dryrun_cell_single_pod(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "smollm-135m", "--shape", "train_4k",
         "--devices", "8", "--mesh", "2,4", "--no-extrapolate",
         "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr
    res = json.load(open(tmp_path / "smollm-135m_train_4k_pod1.json"))
    assert res["status"] == "ok"
    assert res["collectives_scanned"]["total"] > 0


def test_dryrun_cell_multi_pod_mesh(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "smollm-135m", "--shape", "decode_32k",
         "--devices", "8", "--mesh", "2,2,2", "--no-extrapolate",
         "--multi-pod", "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr
    res = json.load(open(tmp_path / "smollm-135m_decode_32k_pod2.json"))
    assert res["status"] == "ok"
    assert res["mesh"] == [2, 2, 2]


def test_pipeline_parallel_matches_sequential():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch import mesh as mesh_lib
from repro.parallel.pipeline import pipeline_forward

mesh = mesh_lib.make_mesh((2, 4), ("pod", "data"))
n_stages = 2
key = jax.random.PRNGKey(0)
stage_params = jax.random.normal(key, (n_stages, 16, 16)) * 0.3
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16))

def stage_fn(w, h):
    return jnp.tanh(h @ w)

y = pipeline_forward(stage_fn, stage_params, x, mesh, axis="pod", n_micro=4)
# sequential reference
ref = x
for s in range(n_stages):
    ref = stage_fn(stage_params[s], ref)
np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5, atol=1e-5)
print("PIPELINE-OK")
""")


def test_compressed_psum_approximates_mean():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch import mesh as mesh_lib
from repro.optim.compress import compressed_psum, init_residuals

mesh = mesh_lib.make_mesh((8,), ("data",))
g = jax.random.normal(jax.random.PRNGKey(0), (8, 64)) * 0.01
res = jnp.zeros((8, 64))

def spmd(g, r):
    avg, new_r = compressed_psum({'w': g[0]}, {'w': r[0]}, 'data', method='int8')
    return avg['w'][None], new_r['w'][None]

avg, new_r = jax.shard_map(
    spmd, mesh=mesh, in_specs=(P('data'), P('data')),
    out_specs=(P('data'), P('data')), check_vma=False)(g, res)
true_mean = jnp.mean(g, axis=0)
# all shards agree and approximate the true mean (int8 quantization)
np.testing.assert_allclose(np.asarray(avg[0]), np.asarray(avg[7]), atol=1e-7)
np.testing.assert_allclose(np.asarray(avg[0]), np.asarray(true_mean), atol=2e-4)
# residuals carry the quantization error
assert float(jnp.abs(new_r).max()) > 0
print("COMPRESS-OK")
""")


def test_elastic_checkpoint_restore_onto_mesh(tmp_path):
    _run(f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import manager as ckpt
from repro.launch import mesh as mesh_lib

tree = {{'w': jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
ckpt.save({str(tmp_path)!r}, 3, tree)

# restore onto an 8-device mesh with TP sharding -- 'elastic' restore
mesh = mesh_lib.make_mesh((2, 4), ("data", "model"))
sh = {{'w': NamedSharding(mesh, P(None, 'model'))}}
restored, step, _ = ckpt.restore({str(tmp_path)!r}, tree, shardings=sh)
assert step == 3
assert restored['w'].sharding.is_equivalent_to(sh['w'], 2)
np.testing.assert_array_equal(np.asarray(restored['w']), np.asarray(tree['w']))
print("ELASTIC-OK")
""")


def test_sharded_train_step_runs_and_matches_single_device():
    """End-to-end: jit train step with planner shardings on a 2x4 mesh
    produces the same loss as the unsharded step."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.launch import mesh as mesh_lib
from repro.models import model as model_lib
from repro.optim.adamw import AdamW, opt_state_shardings
from repro.parallel import sharding as shd
from repro.runtime.trainer import make_train_step

cfg = get_config("smollm-135m").reduced()
shape = ShapeConfig("t", seq_len=32, global_batch=8, kind="train")
mesh = mesh_lib.make_mesh((2, 4), ("data", "model"))
key = jax.random.PRNGKey(0)
params = model_lib.init_params(cfg, key)
opt = AdamW(lr=1e-3)
opt_state = opt.init(params)
batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab_size)}
step = make_train_step(cfg, opt)

# single device reference
p1, o1, m1 = jax.jit(step)(params, opt_state, batch)

pspecs = shd.param_specs(params, mesh)
ns = lambda t: jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), t)
oshard = opt_state_shardings(opt_state, pspecs, mesh, zero1=True)
bshard = ns(shd.batch_spec(cfg, shape, mesh, batch))
with mesh:
    p2, o2, m2 = jax.jit(
        step, in_shardings=(ns(pspecs), oshard, bshard),
        out_shardings=(ns(pspecs), oshard, None),
    )(params, opt_state, batch)
np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-4)
gn1, gn2 = float(m1["grad_norm"]), float(m2["grad_norm"])
np.testing.assert_allclose(gn1, gn2, rtol=1e-3)
print("SHARDED-TRAIN-OK")
""", timeout=900)


def test_moe_ep_path_matches_global():
    """shard_map expert-parallel MoE == global-einsum MoE (fwd + grad)."""
    _run("""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import get_config
from repro.launch import mesh as mesh_lib
from repro.models import moe as moe_lib

cfg = get_config("deepseek-v3-671b").reduced()
# 4 experts % model axis 4 == 0 -> EP path legal
mesh = mesh_lib.make_mesh((2, 4), ("data", "model"))
params = moe_lib.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, cfg.d_model)) * 0.3

def loss_global(p, x):
    y, aux, _ = moe_lib._moe_forward_global(p, x, cfg)
    return jnp.sum(y ** 2) + aux

def loss_auto(p, x):
    y, aux, _ = moe_lib.moe_forward(p, x, cfg)
    return jnp.sum(y ** 2) + aux

l1, g1 = jax.value_and_grad(loss_global)(params, x)
with mesh:
    l2, g2 = jax.jit(jax.value_and_grad(loss_auto))(params, x)
# capacity semantics differ (per-shard vs global) only under overflow;
# with cf=1.25 and uniform-ish routing at this size, results must match
np.testing.assert_allclose(float(l1), float(l2), rtol=2e-3)
for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-2, atol=2e-3)
print("MOE-EP-OK")
""", timeout=900)
