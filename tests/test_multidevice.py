"""Multi-device integration (subprocess with XLA_FLAGS-forced host devices):
sharded-vs-single-device equivalence, compressed collectives, elastic
restore across different meshes."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_forced(n_dev: int, code: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_dev}",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_sharded_train_step_matches_single_device():
    out = run_forced(8, r"""
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from repro.configs import tiny_config
from repro.models.model import Model, param_defs
from repro.models.params import init_params
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.parallel.sharding import axis_rules, defs_to_shardings
from repro.train.step import make_train_step
from repro.data.pipeline import SyntheticLM

cfg = dataclasses.replace(tiny_config("llama2-7b"), dtype="float32",
                          d_model=64, d_ff=128)
model = Model(cfg)
defs = param_defs(cfg)
params = init_params(defs, jax.random.PRNGKey(0))
opt = adamw_init(params)
batch = SyntheticLM(vocab=cfg.vocab_size, seq=16, batch=8).batch_at(0)
step = make_train_step(model, AdamWConfig(warmup_steps=1, total_steps=10),
                       compress_grads=False)
# single device
p1, _, m1 = jax.jit(step)(params, opt, batch)
# 2x4 mesh
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(model=4)
with axis_rules(mesh, None):
    sh = defs_to_shardings(defs)
    params_s = jax.device_put(params, sh)
    opt_s = {"m": jax.device_put(opt["m"], sh),
             "v": jax.device_put(opt["v"], sh), "count": opt["count"]}
    p2, _, m2 = jax.jit(step)(params_s, opt_s, batch)
d = max(float(jnp.abs(a - b).max()) for a, b in zip(
    jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p2)))
print(json.dumps({"max_param_diff": d, "loss1": float(m1["loss"]),
                  "loss2": float(m2["loss"])}))
""")
    res = json.loads(out.strip().splitlines()[-1])
    assert res["max_param_diff"] < 2e-4, res
    assert abs(res["loss1"] - res["loss2"]) < 1e-4


def test_compressed_allreduce_mean():
    out = run_forced(4, r"""
import json
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P
from repro.parallel.compress import compressed_allreduce_mean

mesh = jax.make_mesh((4,), ("data",))
x = jnp.arange(4 * 37, dtype=jnp.float32).reshape(4, 37) / 7.0

def f(xs):
    return compressed_allreduce_mean(xs[0], "data")

got = shard_map(f, mesh=mesh, in_specs=(P("data", None),),
                out_specs=P(), check_vma=False)(x)
ref = x.mean(axis=0)
rel = float(jnp.abs(got - ref).max() / jnp.abs(ref).max())
print(json.dumps({"rel": rel}))
""")
    res = json.loads(out.strip().splitlines()[-1])
    assert res["rel"] < 0.02, res   # int8 AG phase: ~1% quantization error


def test_elastic_restore_across_meshes(tmp_path):
    """Save on a (2,4) mesh, restore onto (4,2) — leaves re-placed by the
    new mesh's rules; training continues (the elastic-restart drill)."""
    out = run_forced(8, rf"""
import dataclasses, json
import jax, jax.numpy as jnp
from repro.configs import tiny_config
from repro.models.model import Model, param_defs
from repro.models.params import init_params
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import axis_rules, defs_to_shardings
from repro.train import checkpoint as ckpt
from repro.train.loop import Trainer, TrainerConfig

d = {str(tmp_path)!r}
cfg = tiny_config("llama2-7b")
from repro.launch.mesh import make_host_mesh
mesh1 = make_host_mesh(model=4)
tr1 = Trainer(cfg, AdamWConfig(warmup_steps=2, total_steps=50),
              TrainerConfig(ckpt_dir=d, ckpt_every=10, ckpt_async=False),
              mesh=mesh1, global_batch=4, seq_len=16)
tr1.run(10)
mesh2 = make_host_mesh(model=2)
tr2 = Trainer(cfg, AdamWConfig(warmup_steps=2, total_steps=50),
              TrainerConfig(ckpt_dir=d, ckpt_every=10, ckpt_async=False),
              mesh=mesh2, global_batch=4, seq_len=16)
step, params, opt = tr2.restore_or_init()
_, _, hist = tr2.run(5)
print(json.dumps({{"restored_step": step, "final": hist[-1]["step"],
                   "loss": hist[-1]["loss"]}}))
""")
    res = json.loads(out.strip().splitlines()[-1])
    assert res["restored_step"] == 10
    assert res["final"] == 15
