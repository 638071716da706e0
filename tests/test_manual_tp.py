"""Numerics of the manual-TP (shard_map) paths vs the GSPMD default.

Runs in a subprocess with 8 forced host devices so a real (data=2, model=4)
mesh exercises all_gather / psum_scatter.  The child inherits the parent's
environment and pins the CPU platform and the device count itself.
"""

import os
import pathlib
import subprocess
import sys

SCRIPT = r"""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.models import lm
from repro.models import params as pm
from repro.models import layers as lay
from repro.sharding.specs import rules_for
from repro.sharding.utils import use_sharding
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_mesh

cfg = dataclasses.replace(
    get_config("llama3.2-1b").reduced(),
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_head=16, d_ff=128,
    vocab_size=512, compute_dtype="float32", remat="none",
)
mesh = make_mesh((2, 4), ("data", "model"))
shape = ShapeConfig("t", 16, 4, "train")
rules = rules_for(cfg, shape, {"data": 2, "model": 4})
rules["act_seq"] = "model"  # force SP so psum_scatter paths engage

params = lm.init_params(cfg, seed=0)
rng = np.random.default_rng(0)
batch = {
    "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32),
    "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32),
}

metas = lm.build_metas(cfg)
pspec = pm.spec_tree(metas, rules)
pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec)
params = jax.device_put(params, pshard)
bshard = {k: NamedSharding(mesh, P(("data",), None)) for k in batch}
batch = jax.device_put(batch, bshard)

def loss(p, b):
    return lm.loss_fn(p, b, cfg)[0]

outs = {}
for name, flags in (
    ("gspmd", (False, False)),
    ("manual", (True, True)),
):
    lay.BF16_TP_REDUCE, lay.MEGATRON_MLP = flags
    with use_sharding(mesh, rules):
        l = jax.jit(loss, in_shardings=(pshard, bshard))(params, batch)
        g = jax.jit(jax.grad(loss), in_shardings=(pshard, bshard))(params, batch)
    outs[name] = (float(l), jax.device_get(g))

l0, g0 = outs["gspmd"]
l1, g1 = outs["manual"]
assert abs(l0 - l1) < 1e-4, (l0, l1)
for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=2e-3, atol=2e-3)
print("MANUAL_TP_OK", l0, l1)
"""


def test_manual_tp_matches_gspmd():
    root = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(root / "src"), env.get("PYTHONPATH")) if p
    )
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        timeout=560,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MANUAL_TP_OK" in out.stdout
