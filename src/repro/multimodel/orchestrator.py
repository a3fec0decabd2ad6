"""SCAR-on-TPU: the paper's scheduler as the placement engine for
multi-model serving on a TPU pod.

Mapping (DESIGN.md sec. 2): chips = chiplets, ICI = NoP, DCN/host = off-chip.
The "dataflow class" heterogeneity becomes *execution-template* heterogeneity:
a chip slot is planned either as part of a TP-major group (weight-stationary
analogue — weights resident, activations stream; right for big-GEMM
transformer layers) or a batch-major group (output-stationary analogue —
activations resident; right for small models / wide batches).  Unlike
silicon dataflow, the template is reconfigurable per window — SCAR's
heterogeneous patterns become *planning priors* rather than hardware facts.

Pipeline:
  1. each requested model's ArchConfig -> SCAR workload IR (layer graph);
  2. the unmodified SCAR engines (greedy packing -> PROV -> SEG -> SCHED)
     run against a pod-as-MCM cost model with TPU constants;
  3. the resulting per-model chip paths are *realized*: each model gets a
     sub-mesh built from exactly those chips and its serve step is lowered
     (and optionally run) there.  SCAR's inter-chiplet pipelining degree
     becomes the sub-mesh parallel width (SPMD prefers TP over pipelining at
     this granularity).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np

from repro.core.chiplet import MCM, ChipletClass, Dataflow, PackageParams
from repro.core.scheduler import SearchConfig, schedule
from repro.core.workload import Model, Scenario, transformer_layers
from repro.models import ModelDims, get_arch
from repro.models.config import ArchConfig

# v5e-flavoured package constants for the pod-as-MCM cost model.
TPU_PKG = PackageParams(
    dram_lat_s=2e-6,           # host/DCN ingest latency
    dram_e_pj_per_bit=20.0,
    dram_bw=100e9,             # host ingest bandwidth
    nop_hop_lat_s=1e-6,        # ICI hop
    nop_e_pj_per_bit=5.0,
    nop_bw=50e9,               # ICI link bandwidth
    clock_hz=750e6,
    mac_e_pj=0.13,
    sram_e_pj_per_bit=0.08,
    l2_bytes_per_cycle=1092.0,  # 819 GB/s HBM @ 750 MHz
    contention_delta=0.05,
)

# n_pe * clock = peak MACs/s = 197 TFLOP/s / 2
TPU_NPE = 131072


def tpu_chip_classes() -> tuple[ChipletClass, ChipletClass]:
    """TP-major (WS analogue) and batch-major (OS analogue) templates."""
    def mk(df):
        return ChipletClass(df, n_pe=TPU_NPE, bw_noc=819e9,
                            bw_mem=819e9, sz_mem=16 * 2**30)
    return mk(Dataflow.NVDLA), mk(Dataflow.SHIDIANNAO)


def make_pod_mcm(rows: int = 16, cols: int = 16,
                 pattern: str = "het_sides") -> MCM:
    from repro.core.chiplet import make_mcm
    base = make_mcm(pattern, rows=rows, cols=cols)
    return MCM(name=f"tpu_pod_{pattern}_{rows}x{cols}", rows=rows, cols=cols,
               class_map=base.class_map, classes=tpu_chip_classes(),
               pkg=TPU_PKG)


def arch_to_workload(cfg: ArchConfig, batch: int, seq: int) -> Model:
    """ArchConfig -> SCAR layer graph (transformer-equivalent accounting for
    ssm/lstm blocks: their projections are GEMMs of the same shapes)."""
    d_ff = cfg.d_ff if cfg.d_ff else 4 * cfg.d_model
    if cfg.moe is not None:
        d_ff = cfg.moe.top_k * cfg.moe.expert_d_ff + (
            cfg.moe.n_shared_experts * cfg.moe.expert_d_ff)
        if cfg.moe.dense_residual:
            d_ff += cfg.moe.dense_d_ff
    layers = transformer_layers(
        cfg.name, n_blocks=cfg.n_layers, d_model=cfg.d_model,
        n_heads=cfg.n_heads, d_ff=max(d_ff, cfg.d_model),
        seq=seq, batch=batch, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd)
    return Model(cfg.name, tuple(layers), batch)


@dataclasses.dataclass
class ServeRequest:
    arch: str
    batch: int
    seq: int


@dataclasses.dataclass
class ModelPlacement:
    arch: str
    window: int
    chips: tuple[int, ...]       # chip ids, row-major over the pod grid
    template: str                # tp-major | batch-major | mixed


@dataclasses.dataclass
class PodPlan:
    outcome: object              # core ScheduleOutcome
    placements: list[ModelPlacement]
    rows: int
    cols: int


def plan(requests: list[ServeRequest], rows: int = 16, cols: int = 16,
         pattern: str = "het_sides", metric: str = "edp",
         cfg: Optional[SearchConfig] = None) -> PodPlan:
    """Run the SCAR engines over the pod and return chip placements."""
    mcm = make_pod_mcm(rows, cols, pattern)
    models = tuple(arch_to_workload(get_arch(r.arch), r.batch, r.seq)
                   for r in requests)
    sc = Scenario("pod_serving", models)
    out = schedule(sc, mcm, cfg or SearchConfig(metric=metric))
    placements = []
    for w, wr in enumerate(out.windows):
        for p in wr.plan.plans:
            classes = {mcm.class_of(c).dataflow for c in p.chiplets}
            template = ("tp-major" if classes == {Dataflow.NVDLA} else
                        "batch-major" if classes == {Dataflow.SHIDIANNAO}
                        else "mixed")
            placements.append(ModelPlacement(
                arch=requests[p.model_idx].arch, window=w,
                chips=p.chiplets, template=template))
    return PodPlan(outcome=out, placements=placements, rows=rows, cols=cols)


def realize(plan_: PodPlan, requests: list[ServeRequest], devices=None,
            window: int = 0, reduced_archs: bool = False):
    """Build a sub-mesh per placement in ``window`` and lower each model's
    prefill step on its own chips.  Returns {arch: (mesh, lowered)}."""
    from repro.distributed import sharding as shd
    from repro.models.steps import make_prefill_step
    from repro.models.testing import reduced, synth_batch

    devices = devices if devices is not None else np.array(
        jax.devices()).reshape(plan_.rows, plan_.cols)
    out = {}
    for pl_ in plan_.placements:
        if pl_.window != window:
            continue
        req = next(r for r in requests if r.arch == pl_.arch)
        cfg = get_arch(pl_.arch)
        if reduced_archs:
            cfg = reduced(cfg)
        coords = [divmod(c, plan_.cols) for c in pl_.chips]
        devs = np.array([devices[r, c] for r, c in coords])
        n = len(devs)
        tp = n if (cfg.n_heads % n == 0 and shd.style_for(cfg) == "tp") else 1
        mesh = jax.sharding.Mesh(
            devs.reshape(n // tp if tp > 1 else n, tp if tp > 1 else 1),
            ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        dims = ModelDims.create(cfg, tp=tp)
        batch = max(req.batch, n // tp) if tp == 1 else req.batch
        specs = shd.make_specs(cfg, mesh, batch)
        fn = make_prefill_step(cfg, dims, max_cache_len=req.seq, specs=specs)
        with jax.set_mesh(mesh):
            b = synth_batch(cfg, batch=batch, seq=req.seq) \
                if reduced_archs else None
            if b is not None:
                b.pop("labels", None)
                import jax as _jax
                pshapes = _jax.eval_shape(
                    lambda: __import__("repro.models", fromlist=["x"])
                    .init_params(cfg, _jax.random.PRNGKey(0), dims))
                lowered = _jax.jit(fn).lower(pshapes, b)
            else:
                from repro.launch.cells import param_shapes
                pshapes = param_shapes(cfg, dims)
                import jax.numpy as jnp
                binputs = {"tokens": jax.ShapeDtypeStruct(
                    (batch, req.seq), jnp.int32)}
                lowered = jax.jit(fn).lower(pshapes, binputs)
            out[pl_.arch] = (mesh, lowered.compile())
    return out
