"""Sharding rule engine + a real multi-device lower/compile (subprocess —
the main pytest process must keep seeing 1 device)."""
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.distributed import sharding
from repro.launch.mesh import local_mesh
from repro.models import lm


class FakeMesh:
    """Shape-only stand-in (sharding rules never touch devices)."""

    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


MESH = FakeMesh({"data": 16, "model": 16})


def _abstract_params(arch):
    cfg = configs.get(arch)
    return cfg, jax.eval_shape(
        lambda: lm.init_params(jax.random.PRNGKey(0), cfg))


def _find(specs, params, *path):
    node_s, node_p = specs, params
    for k in path:
        node_s, node_p = node_s[k], node_p[k]
    return node_s, node_p


def test_divisibility_rules_smollm():
    """smollm: 15 heads / 5 kv heads do NOT divide 16 → replicated; its
    d_ff=2560 and vocab=49152 DO divide → sharded."""
    cfg, params = _abstract_params("smollm-360m")
    specs = sharding.param_specs(params, MESH)
    s, p = _find(specs, params, "stack", "scanned", "slot0", "attn", "wq")
    assert s[-2] is None                        # 15 heads: NOT head-sharded
    assert s[-3] == "model"                     # falls back to d_model (960)
    s, _ = _find(specs, params, "stack", "scanned", "slot0", "mlp", "w_gate")
    assert s[-1] == "model"                      # 2560 % 16 == 0
    s, _ = _find(specs, params, "embed")
    assert s[0] == "model"                       # vocab sharded


def test_ep_rules_deepseek():
    """deepseek: 64 experts divide 16 → expert-parallel on the expert dim."""
    cfg, params = _abstract_params("deepseek-v2-lite-16b")
    specs = sharding.param_specs(params, MESH)
    s, p = _find(specs, params, "stack", "scanned", "slot0", "moe", "w_gate")
    assert s[-3] == "model" and p.shape[-3] == 64


def test_moe_fallback_mixtral():
    """mixtral: 8 experts don't divide 16 → falls back to d_ff sharding."""
    cfg, params = _abstract_params("mixtral-8x22b")
    specs = sharding.param_specs(params, MESH)
    s, p = _find(specs, params, "stack", "scanned", "slot0", "moe", "w_gate")
    assert s[-3] is None and s[-1] == "model"


def test_zero_specs_add_data_axis():
    cfg, params = _abstract_params("olmo-1b")
    pspecs = sharding.param_specs(params, MESH)
    zspecs = sharding.zero_specs(params, pspecs, MESH)
    s, p = _find(zspecs, params, "stack", "scanned", "slot0", "mlp",
                 "w_gate")
    assert "data" in s and "model" in s         # ZeRO + TP


def test_strategies():
    cfg, params = _abstract_params("smollm-360m")
    dp = sharding.param_specs(params, MESH, "dp")
    # dp replicates everything EXCEPT embed/head (vocab must stay sharded
    # or the (B,S,V) logits materialize unsharded — EXPERIMENTS.md §Perf P1)
    assert dp["embed"][0] == "model"
    assert all(all(e is None for e in s)
               for s in jax.tree.leaves(dp["stack"], is_leaf=lambda x:
                                        isinstance(x, P)))
    fsdp = sharding.param_specs(params, MESH, "fsdp")
    s, _ = _find(fsdp, params, "stack", "scanned", "slot0", "mlp", "w_gate")
    assert "data" in s and "model" not in s


def test_real_compile_on_multidevice_mesh():
    """Subprocess with 8 host devices: lower+compile a smoke train step on a
    (4,2) mesh — catches real GSPMD errors the FakeMesh tests can't."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, dataclasses
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.distributed import sharding
from repro.train import loop as train_loop

cfg = dataclasses.replace(configs.get_smoke("smollm-360m"), dtype="float32")
tcfg = train_loop.TrainConfig(microbatches=1, remat=True)
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
state = jax.eval_shape(lambda: train_loop.init_state(
    jax.random.PRNGKey(0), cfg, tcfg))
batch = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
         "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
pspec = sharding.param_specs(state["params"], mesh)
mspec = sharding.zero_specs(state["opt"]["m"], pspec, mesh)
state_spec = {"params": pspec, "opt": {"m": mspec, "v": mspec,
              "step": P()}, "step": P()}
bspec = sharding.batch_specs(batch, mesh, ("data",))
named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                               is_leaf=lambda x: isinstance(x, P))
with mesh:
    lowered = jax.jit(
        lambda s, b: train_loop.train_step(s, b, cfg, tcfg),
        in_shardings=(named(state_spec), named(bspec))).lower(state, batch)
    compiled = lowered.compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0
print("COMPILE_OK")
"""
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=".", timeout=600)
    assert "COMPILE_OK" in out.stdout, out.stderr[-2000:]
