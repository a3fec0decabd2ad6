import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (architecture x shape) cell on the
production meshes and record memory/cost/collective statistics.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --mesh single --out results.jsonl
  PYTHONPATH=src python -m repro.launch.dryrun --arch minitron-8b --shape train_4k

Results append to JSONL (one record per cell x mesh); already-recorded cells
are skipped, so the sweep is resumable after interruption.
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed import sharding as shd
from repro.launch import cells as cellmod
from repro.launch.mesh import make_production_mesh
from repro.models import ModelDims, get_arch, make_train_step
from repro.models.steps import make_decode_step, make_prefill_step
from repro.optim import AdamWConfig, adamw

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "f8e4m3": 1,
                "f8e5m2": 1, "s16": 2, "u16": 2}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

# bf16-moment (low-memory) optimizer for the largest models
LOW_MEM_OPT = {"arctic-480b", "llama-3.2-vision-90b", "command-r-35b",
               "qwen2.5-32b"}


def _type_bytes(type_str: str) -> float:
    m = re.match(r"(\w+?)\[([\d,]*)\]", type_str)
    if not m:
        return 0.0
    dt, dims = m.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Per-device collective traffic from the post-SPMD HLO.

    The SPMD module is the per-device program, so result shapes are
    per-device.  Operand bytes are derived per op semantics (all-gather
    operand = result/group, reduce-scatter operand = result*group); we also
    estimate ring link-bytes per device: all-reduce ~ 2*size*(g-1)/g,
    all-gather/reduce-scatter ~ size*(g-1)/g, all-to-all/permute ~ size.
    """
    out = {c: 0.0 for c in COLLECTIVES}
    counts = {c: 0 for c in COLLECTIVES}
    link = {c: 0.0 for c in COLLECTIVES}
    by_group: dict[str, float] = {}
    pat = re.compile(
        r"=\s*(\([^)]*\)|\S+)\s+(" + "|".join(COLLECTIVES) +
        r")(?:-start)?\(")
    grp_pat = re.compile(r"replica_groups=(\[(\d+),(\d+)\]|\{\{[^}]*\}[^\n]*?\})")
    for line in hlo_text.splitlines():
        m = pat.search(line)
        if not m:
            continue
        res_str, op = m.group(1), m.group(2)
        size = sum(_type_bytes(t)
                   for t in re.findall(r"\b\w+\[[\d,]*\]", res_str))
        g = 1
        gm = grp_pat.search(line)
        if gm:
            if gm.group(3):
                g = int(gm.group(3))
            else:
                first = gm.group(1).split("}")[0]
                g = first.count(",") + 1
        if op == "all-gather":
            operand = size / max(g, 1)
            lb = size * (g - 1) / max(g, 1)
        elif op == "reduce-scatter":
            operand = size * g
            lb = size * (g - 1)
        elif op == "all-reduce":
            operand = size
            lb = 2.0 * size * (g - 1) / max(g, 1)
        else:  # all-to-all, collective-permute
            operand = size
            lb = size
        out[op] += operand
        link[op] += lb
        counts[op] += 1
        key = f"{op}:g{g}"
        by_group[key] = by_group.get(key, 0.0) + lb
    return {"operand_bytes": out, "counts": counts,
            "link_bytes": link, "by_group": by_group,
            "total_bytes": sum(out.values()),
            "total_link_bytes": sum(link.values())}


def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def cache_spec_tree(cfg, cell, cache_shapes, specs):
    """PartitionSpec tree for the (stacked) decode cache."""
    def f(path, leaf):
        names = [getattr(k, "key", str(k)) for k in path]
        if leaf.ndim == 5 and names[-1] in ("k", "v"):
            return specs.kv_cache_stacked
        # ssm / lstm states & conv windows: batch-sharded over data only
        dp = specs.kv_cache_stacked[1]
        return P(None, dp, *([None] * (leaf.ndim - 2)))
    return jax.tree_util.tree_map_with_path(f, cache_shapes)


def analytic_memory(cell: cellmod.Cell, mesh) -> dict:
    """Per-device memory from first principles (backend-independent).

    The CPU backend's temp numbers are conservative (f32-materialised
    attention temps that the TPU backend fuses / the Pallas flash kernel
    eliminates), so v5e fit is judged on this model: sharded params +
    optimizer moments + gradient shard + KV cache + scan activation carry +
    the largest single transient (attention score chunk / logits chunk).
    """
    cfg = get_arch(cell.arch)
    style = shd.style_for(cfg)
    n_dev = mesh.devices.size
    model_sz = mesh.devices.shape[-1]
    data_sz = mesh.devices.shape[-2]
    pod_sz = mesh.devices.shape[0] if len(mesh.devices.shape) == 3 else 1
    tp = model_sz if style == "tp" else 1
    dims = ModelDims.create(cfg, tp=tp)
    p_global = cfg.param_count() * 2.0              # bf16
    fsdp = cell.arch in shd.FSDP_ARCHS
    p_shards = (model_sz * data_sz if fsdp
                else (model_sz if style == "tp" else 1))
    p_dev = p_global / p_shards
    out = {"params": p_dev}
    B = cell.batch
    # batch shards over every axis that divides it (mirrors _dp_axes)
    dp = 1
    for ax_sz in ([pod_sz, data_sz] if pod_sz > 1 else [data_sz]) + \
            ([model_sz] if style == "dp" else []):
        if B % (dp * ax_sz) == 0:
            dp *= ax_sz
    B_loc = max(1, B // dp)
    d = cfg.d_model
    if cell.kind == "train":
        mom = 2 if cell.arch in LOW_MEM_OPT else 4
        out["opt_moments"] = 2 * cfg.param_count() * mom / (model_sz * data_sz
                                                            if style == "tp"
                                                            else n_dev)
        # accumulator dtype follows the optimizer's moment dtype
        out["grads"] = p_dev * (1.0 if cell.arch in LOW_MEM_OPT else 2.0)
        accum = accum_steps_for(cell, mesh)
        out["accum_steps"] = accum
        micro_b = max(1, B_loc // accum)
        B_loc = micro_b
        out["act_carry"] = cfg.n_super_blocks * B_loc * cell.seq * d * 2.0
        h_shard = model_sz if (style == "tp" or
                               (cfg.n_heads % model_sz == 0)) else 1
        h_loc = max(1, dims.n_q_pad // h_shard)
        out["attn_transient"] = (B_loc * h_loc * min(cfg.attn_q_chunk,
                                                     cell.seq) * cell.seq * 4.0
                                 if cfg.d_ff or cfg.n_heads else 0.0)
        v_loc = dims.vocab_pad / (model_sz if style == "tp" else 1)
        out["logits_chunk"] = B_loc * min(512, cell.seq) * v_loc * 4.0 * 2
    else:
        n_attn = sum(1 for k in cfg.block_pattern
                     if k.value in ("attn", "moe", "cross_attn",
                                    "shared_attn")) * cfg.n_super_blocks
        kv_heads_loc = max(1, dims.n_kv_pad // model_sz)
        kv_batch_loc = B_loc if not cell.seq_shard else 1
        kv_seq_loc = cell.seq / (data_sz if cell.seq_shard else 1)
        out["kv_cache"] = (2.0 * n_attn * kv_batch_loc * kv_seq_loc
                           * kv_heads_loc * cfg.hd * 2.0)
        if cell.kind == "prefill":
            h_shard = model_sz if (style == "tp" or
                                   (cfg.n_heads % model_sz == 0)) else 1
            h_loc = max(1, dims.n_q_pad // h_shard)
            out["attn_transient"] = (B_loc * h_loc
                                     * min(cfg.attn_q_chunk, cell.seq)
                                     * cell.seq * 4.0)
    out["total"] = sum(out.values())
    out["fits_v5e_16g"] = bool(out["total"] < 16 * 2**30)
    return {k: (round(v, 1) if isinstance(v, float) else v)
            for k, v in out.items()}


def _dp_total(cell: cellmod.Cell, mesh) -> int:
    cfg = get_arch(cell.arch)
    style = shd.style_for(cfg)
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes = shd._dp_axes(tuple(mesh.axis_names), cell.batch, shape, style)
    dp = 1
    for a in axes:
        dp *= shape[a]
    return dp


def accum_steps_for(cell: cellmod.Cell, mesh,
                    target_micro_per_device: int | None = None) -> int:
    """Gradient-accumulation depth: microbatch ~2 sequences per device
    (1 for the 480B MoE, whose activations are the fit-limiting term)."""
    if target_micro_per_device is None:
        target_micro_per_device = 1 if cell.arch == "arctic-480b" else 2
    dp = _dp_total(cell, mesh)
    b_loc = max(1, cell.batch // dp)
    accum = max(1, b_loc // target_micro_per_device)
    while accum > 1 and (cell.batch % (accum * dp) != 0):
        accum -= 1
    return accum


def build_cell(cell: cellmod.Cell, mesh, overrides: dict | None = None):
    """Returns (fn, arg_specs, in_shardings, out_shardings|None).

    ``overrides`` (perf-iteration knobs): seq_parallel, remat_policy,
    expert_axes, q_chunk, accum_steps.
    """
    ov = overrides or {}
    cfg = get_arch(cell.arch)
    import dataclasses as _dc
    if "q_chunk" in ov:
        cfg = _dc.replace(cfg, attn_q_chunk=ov["q_chunk"])
    if cfg.moe is not None and ("moe_group" in ov or "moe_capacity" in ov):
        moe = _dc.replace(cfg.moe,
                          group_size=ov.get("moe_group",
                                            cfg.moe.group_size),
                          capacity_factor=ov.get("moe_capacity",
                                                 cfg.moe.capacity_factor))
        cfg = _dc.replace(cfg, moe=moe)
    tp = mesh.devices.shape[-1] if shd.style_for(cfg) == "tp" else 1
    dims = ModelDims.create(cfg, tp=tp)
    specs = shd.make_specs(cfg, mesh, cell.batch, seq_shard=cell.seq_shard,
                           seq_parallel=ov.get("seq_parallel", False),
                           expert_axes=ov.get("expert_axes", "default"))
    pshapes = cellmod.param_shapes(cfg, dims, jnp.bfloat16)
    pspec = shd.param_specs(cfg, pshapes)
    p_shard = _ns(mesh, pspec)
    binputs = cellmod.input_specs(cell)

    if cell.kind == "train":
        opt = AdamWConfig(moment_dtype=jnp.bfloat16
                          if cell.arch in LOW_MEM_OPT else jnp.float32)
        oshapes = jax.eval_shape(lambda: adamw.init_state(opt, pshapes))
        ospec = shd.opt_state_specs(cfg, pshapes, oshapes,
                                    mesh.devices.shape[-2]
                                    if "data" in mesh.axis_names else 1)
        o_shard = _ns(mesh, ospec)
        b_shard = _ns(mesh, shd.batch_specs(cfg, mesh, binputs, cell.batch))
        accum = ov.get("accum_steps", accum_steps_for(cell, mesh))
        fn = make_train_step(cfg, dims, opt, specs=specs, remat=True,
                             accum_steps=accum,
                             remat_policy=ov.get("remat_policy", "nothing"))
        in_sh = (p_shard, o_shard, b_shard)
        out_sh = (p_shard, o_shard, None)
        args = (pshapes, oshapes, binputs)
        donate = (0, 1)
    elif cell.kind == "prefill":
        fn = make_prefill_step(cfg, dims, max_cache_len=cell.seq, specs=specs)
        b_shard = _ns(mesh, shd.batch_specs(cfg, mesh, binputs, cell.batch))
        cshapes = cellmod.cache_specs(cell, dims)
        cspec = cache_spec_tree(cfg, cell, cshapes, specs)
        logits_sh = NamedSharding(mesh, P(specs.logits[0], specs.logits[2]))
        in_sh = (p_shard, b_shard)
        out_sh = (logits_sh, _ns(mesh, cspec))
        args = (pshapes, binputs)
        donate = ()
    else:  # decode
        fn0 = make_decode_step(cfg, dims, specs=specs)
        kv_dtype = {"bf16": jnp.bfloat16,
                    "f8": jnp.float8_e4m3fn}[ov.get("kv_dtype", "bf16")]
        cshapes = cellmod.cache_specs(cell, dims, dtype=kv_dtype)
        cspec = cache_spec_tree(cfg, cell, cshapes, specs)
        c_shard = _ns(mesh, cspec)
        tok_sh = NamedSharding(mesh, P(specs.act[0], None))
        idx_sh = NamedSharding(mesh, P())
        logits_sh = NamedSharding(mesh, P(specs.logits[0], specs.logits[2]))
        if cfg.cross_ctx_len:
            def fn(params, tokens, cache, index, cross_ctx):
                return fn0(params, tokens, cache, index, cross_ctx)
            ctx_spec = cellmod.input_specs(cell)["cross_ctx"]
            ctx_sh = NamedSharding(mesh, P(specs.act[0], None, None))
            in_sh = (p_shard, tok_sh, c_shard, idx_sh, ctx_sh)
            args = (pshapes, cellmod.input_specs(cell)["tokens"], cshapes,
                    cellmod.input_specs(cell)["index"], ctx_spec)
        else:
            def fn(params, tokens, cache, index):
                return fn0(params, tokens, cache, index)
            in_sh = (p_shard, tok_sh, c_shard, idx_sh)
            args = (pshapes, cellmod.input_specs(cell)["tokens"], cshapes,
                    cellmod.input_specs(cell)["index"])
        out_sh = (logits_sh, c_shard)
        donate = (2,)
    return fn, args, in_sh, out_sh, donate


def run_cell(cell: cellmod.Cell, mesh, mesh_name: str,
             overrides: dict | None = None) -> dict:
    rec = {"arch": cell.arch, "shape": cell.shape, "mesh": mesh_name,
           "kind": cell.kind}
    if overrides:
        rec["overrides"] = overrides
    t0 = time.time()
    fn, args, in_sh, out_sh, donate = build_cell(cell, mesh, overrides)
    jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=donate)
    with jax.set_mesh(mesh):
        lowered = jitted.lower(*args)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)
    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_per_device": int(ma.argument_size_in_bytes
                               + ma.temp_size_in_bytes
                               + ma.output_size_in_bytes
                               - ma.alias_size_in_bytes),
    }
    ca = compiled.cost_analysis() or {}
    # raw XLA numbers (while bodies counted ONCE — kept for reference)
    rec["cost_xla_raw"] = {"flops": float(ca.get("flops", 0.0)),
                           "bytes_accessed": float(ca.get("bytes accessed",
                                                          0.0))}
    # trip-count-aware analysis (the roofline source of truth)
    from repro.analysis import hlo_cost
    hlo_text = compiled.as_text()
    hc = hlo_cost.analyze(hlo_text)
    rec["cost"] = {"flops": hc.flops, "bytes_accessed": hc.bytes_accessed}
    rec["collectives"] = {
        "operand_bytes": hc.collective_operand_bytes,
        "link_bytes": hc.collective_link_bytes,
        "by_group": hc.by_collective,
        "loops": hc.loops[:20],
        "total_bytes": hc.collective_operand_bytes,
        "total_link_bytes": hc.collective_link_bytes,
    }
    rec["analytic_memory"] = analytic_memory(cell, mesh)
    print(f"[dryrun] {cell.arch} x {cell.shape} x {mesh_name}: "
          f"compile={rec['compile_s']}s "
          f"flops/dev={rec['cost']['flops']:.3e} "
          f"peak/dev={rec['memory']['peak_per_device']/2**30:.2f}GiB "
          f"coll_link={rec['collectives']['total_link_bytes']:.3e}B",
          flush=True)
    return rec


def _cell_size_key(cell: cellmod.Cell) -> float:
    cfg = get_arch(cell.arch)
    return cfg.param_count() * (2.0 if cell.kind == "train" else 1.0) \
        + cell.batch * cell.seq * 1e3


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default=None, help="JSONL append path")
    ap.add_argument("--order", default="small-first",
                    choices=["small-first", "as-is"])
    args = ap.parse_args()

    done: set[tuple] = set()
    if args.out and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if "error" not in r:
                        done.add((r["arch"], r["shape"], r["mesh"]))
                except json.JSONDecodeError:
                    pass

    todo = cellmod.all_cells()
    if args.arch:
        todo = [c for c in todo if c.arch == args.arch]
    if args.shape:
        todo = [c for c in todo if c.shape == args.shape]
    if args.order == "small-first":
        todo.sort(key=_cell_size_key)
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh()))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16",
                       make_production_mesh(multi_pod=True)))

    n_fail = 0
    for mesh_name, mesh in meshes:
        for cell in todo:
            if (cell.arch, cell.shape, mesh_name) in done:
                continue
            try:
                rec = run_cell(cell, mesh, mesh_name)
            except Exception as e:  # noqa: BLE001 - record and continue
                traceback.print_exc()
                rec = {"arch": cell.arch, "shape": cell.shape,
                       "mesh": mesh_name, "error": repr(e)[:500]}
                n_fail += 1
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
    skipped = [c for c in cellmod.all_cells(include_skipped=True)
               if not cellmod.cell_valid(c)[0]]
    print(f"[dryrun] complete; {n_fail} failures; "
          f"{len(skipped)} cells skipped by validity rules:")
    for c in skipped:
        print(f"  SKIP {c.arch} x {c.shape}: {cellmod.cell_valid(c)[1]}")


if __name__ == "__main__":
    main()
