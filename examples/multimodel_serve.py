"""SCAR-on-TPU end-to-end: schedule three models onto one device grid, build
a sub-mesh per model from exactly the chips the scheduler picked, and run a
prefill on each.

    PYTHONPATH=src python examples/multimodel_serve.py

Runs on 8 emulated host devices (4x2 "pod"); on real hardware the same code
places onto the 16x16 pod.
"""
import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
import sys

sys.path.insert(0, "src")

import jax

from jax.sharding import AxisType
import jax.numpy as jnp
import numpy as np

from repro.core.scheduler import SearchConfig
from repro.distributed import sharding as shd
from repro.models import ModelDims, get_arch, init_params
from repro.models.steps import make_prefill_step
from repro.models.testing import reduced, synth_batch
from repro.multimodel import ServeRequest, plan
from repro.launch.platform import use_compile_cache


def main() -> None:
    use_compile_cache(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    rows, cols = 4, 2
    reqs = [ServeRequest("minitron-8b", batch=4, seq=64),
            ServeRequest("qwen2-moe-a2.7b", batch=4, seq=64),
            ServeRequest("xlstm-350m", batch=4, seq=64)]
    pod = plan(reqs, rows=rows, cols=cols, pattern="het_sides",
               cfg=SearchConfig(metric="edp", n_splits=0,
                                max_nodes_per_model=4))
    print(f"pod plan: {len(pod.placements)} placements, "
          f"EDP={pod.outcome.edp:.4g}")
    devices = np.array(jax.devices()).reshape(rows, cols)

    for pl_ in pod.placements:
        if pl_.window != 0:
            continue
        req = next(r for r in reqs if r.arch == pl_.arch)
        cfg = reduced(get_arch(pl_.arch))
        coords = [divmod(c, cols) for c in pl_.chips]
        devs = np.array([devices[r, c] for r, c in coords])
        mesh = jax.sharding.Mesh(devs.reshape(len(devs), 1),
                                 ("data", "model"),
                                 axis_types=(AxisType.Auto,) * 2)
        dims = ModelDims.create(cfg, tp=1)
        batch = max(req.batch, len(devs))
        specs = shd.make_specs(cfg, mesh, batch)
        with jax.set_mesh(mesh):
            params = init_params(cfg, jax.random.PRNGKey(0), dims)
            b = synth_batch(cfg, batch=batch, seq=req.seq)
            b.pop("labels", None)
            fn = jax.jit(make_prefill_step(cfg, dims, max_cache_len=req.seq,
                                           specs=specs))
            logits, cache = fn(params, b)
            print(f"  {pl_.arch:18s} window 0 chips={pl_.chips} "
                  f"template={pl_.template} -> prefill logits "
                  f"{tuple(logits.shape)} finite="
                  f"{bool(jnp.isfinite(logits.astype(jnp.float32)).all())}")
    print("multi-model serving placement realized and executed.")


if __name__ == "__main__":
    main()
