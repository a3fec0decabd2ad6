"""Multi-device integration tests (8 emulated host devices, subprocess —
the main test process must keep seeing 1 device)."""
import os
import subprocess
import sys

import pytest

_SCRIPT_COMPRESS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.distributed.compress import compressed_psum
from jax.sharding import AxisType
n = len(jax.devices()); assert n == 8, n
mesh = jax.make_mesh((n,), ("pod",), axis_types=(AxisType.Auto,))
x = jax.random.normal(jax.random.PRNGKey(0), (4096,))
with jax.set_mesh(mesh):
    out = compressed_psum(x, mesh, axis="pod")
exact = x * n
rel = float(jnp.max(jnp.abs(out - exact)) / jnp.max(jnp.abs(exact)))
assert rel < 0.02, f"int8 ring all-reduce error too large: {rel}"
print("COMPRESS_OK", rel)
"""

_SCRIPT_SHARDED_TRAIN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from repro.distributed import sharding as shd
from jax.sharding import AxisType
from repro.models import ModelDims, get_arch, init_params, make_train_step
from repro.models.testing import reduced, synth_batch
from repro.optim import AdamWConfig, adamw

cfg = reduced(get_arch("minitron-8b"))
mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
dims = ModelDims.create(cfg, tp=2)
specs = shd.make_specs(cfg, mesh, 8)
opt = AdamWConfig(lr=1e-2, warmup_steps=1, total_steps=50)
with jax.set_mesh(mesh):
    params = init_params(cfg, jax.random.PRNGKey(0), dims)
    pspec = shd.param_specs(cfg, params)
    params = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, pspec)
    state = adamw.init_state(opt, params)
    step = jax.jit(make_train_step(cfg, dims, opt, specs=specs,
                                   accum_steps=2))
    batch = synth_batch(cfg, batch=8, seq=32)
    losses = []
    for _ in range(3):
        params, state, m = step(params, state, batch)
        losses.append(float(m["loss"]))
assert all(np.isfinite(l) for l in losses), losses
assert losses[-1] < losses[0], losses
# check a TP-sharded weight really is distributed
leaf = params["layers"]["p0"]["attn"]["wq"]["w"]
assert len(leaf.sharding.device_set) > 1
print("TRAIN_OK", losses[0], "->", losses[-1])
"""


def _run(script: str) -> str:
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=540, cwd=os.getcwd())
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_int8_ring_allreduce_on_8_devices():
    assert "COMPRESS_OK" in _run(_SCRIPT_COMPRESS)


@pytest.mark.slow
def test_sharded_train_step_on_4x2_mesh():
    assert "TRAIN_OK" in _run(_SCRIPT_SHARDED_TRAIN)
