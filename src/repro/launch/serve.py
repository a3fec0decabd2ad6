"""Serving driver: prefill + batched autoregressive decode.

  PYTHONPATH=src python -m repro.launch.serve --arch xlstm-350m --smoke \
      --batch 4 --prompt-len 32 --gen 16

Same code path as production serving: jitted prefill fills the cache, the
decode step is jitted once and iterated; works on the test mesh (CPU) and on
``make_production_mesh()`` unchanged.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh, make_test_mesh
from repro.models import ModelDims, get_arch, init_params
from repro.models.steps import make_decode_step, make_prefill_step
from repro.models.testing import reduced, synth_batch


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--mesh", choices=["test", "prod"], default="test")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    if cfg.encoder_only:
        raise SystemExit(f"{cfg.name} is encoder-only; no decode serving")
    mesh = (make_production_mesh() if args.mesh == "prod"
            else make_test_mesh())
    tp = mesh.devices.shape[-1] if shd.style_for(cfg) == "tp" else 1
    dims = ModelDims.create(cfg, tp=tp)
    max_len = args.prompt_len + args.gen
    specs = shd.make_specs(cfg, mesh, args.batch)

    with jax.set_mesh(mesh):
        params = init_params(cfg, jax.random.PRNGKey(args.seed), dims)
        batch = synth_batch(cfg, batch=args.batch, seq=args.prompt_len,
                            seed=args.seed)
        batch.pop("labels", None)
        cross = batch.get("cross_ctx")
        prefill = jax.jit(make_prefill_step(cfg, dims, max_cache_len=max_len,
                                            specs=specs))
        decode = jax.jit(make_decode_step(cfg, dims, specs=specs),
                         donate_argnums=(2,))
        t0 = time.time()
        logits, cache = prefill(params, batch)
        tokens = [jnp.argmax(logits, axis=-1)[:, None]]
        prefill_s = time.time() - t0
        t0 = time.time()
        key = jax.random.PRNGKey(args.seed + 1)
        for i in range(args.gen - 1):
            logits, cache = decode(params, tokens[-1], cache,
                                   jnp.int32(args.prompt_len + i), cross)
            if args.temperature > 0:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(
                    sub, logits / args.temperature, axis=-1)[:, None]
            else:
                nxt = jnp.argmax(logits, axis=-1)[:, None]
            tokens.append(nxt)
        decode_s = time.time() - t0
    out = jnp.concatenate(tokens, axis=1)
    tok_per_s = args.batch * (args.gen - 1) / max(decode_s, 1e-9)
    print(f"[serve] {cfg.name}: prefill({args.batch}x{args.prompt_len})="
          f"{prefill_s*1e3:.1f}ms decode {args.gen - 1} steps -> "
          f"{tok_per_s:.1f} tok/s; sample tokens {out[0, :8].tolist()}")
    return {"tokens": out, "prefill_s": prefill_s, "decode_s": decode_s}


if __name__ == "__main__":
    main()
