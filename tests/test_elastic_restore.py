"""Elastic scaling: checkpoints restore across device-count changes
(subprocess pairs with different host-device counts)."""
import os
import subprocess
import sys


def _run(code):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=".", timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


def test_restore_onto_different_mesh(tmp_path):
    """Save with 4 devices / (2,2) mesh, restore with 8 devices / (2,4):
    the checkpoint stores full arrays, restore re-shards to the new mesh."""
    ckpt = str(tmp_path / "elastic")
    save_code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, dataclasses
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.checkpoint.manager import CheckpointManager
from repro.models import lm

cfg = dataclasses.replace(configs.get_smoke("olmo-1b"), dtype="float32")
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
params = lm.init_params(jax.random.PRNGKey(7), cfg)
from repro.distributed import sharding
specs = sharding.param_specs(params, mesh)
params = jax.tree.map(
    lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, specs,
    is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, P))
mgr = CheckpointManager({ckpt!r})
mgr.save(3, params)
print("SAVED", float(jax.tree.leaves(params)[0].sum()))
"""
    out1 = _run(save_code)
    saved_sum = float(out1.split("SAVED")[1].strip())

    restore_code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, dataclasses
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.checkpoint.manager import CheckpointManager
from repro.distributed import sharding
from repro.models import lm

cfg = dataclasses.replace(configs.get_smoke("olmo-1b"), dtype="float32")
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
like = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
specs = sharding.param_specs(like, mesh)
shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                         is_leaf=lambda x: isinstance(x, P))
mgr = CheckpointManager({ckpt!r})
params = mgr.restore(like, shardings=shardings)
leaf = jax.tree.leaves(params)[0]
assert len(leaf.sharding.device_set) in (1, 2, 4, 8)
print("RESTORED", float(leaf.sum()))
"""
    out2 = _run(restore_code)
    restored_sum = float(out2.split("RESTORED")[1].strip())
    assert abs(saved_sum - restored_sum) < 1e-3 * max(1, abs(saved_sum))
