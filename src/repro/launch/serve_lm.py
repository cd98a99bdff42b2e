"""LM serving driver: batched prefill + decode loop on local devices.

  PYTHONPATH=src python -m repro.launch.serve_lm --arch qwen1.5-4b --smoke \
      --batch 4 --prompt-len 64 --gen 32

(The FPTC archive service lives in :mod:`repro.launch.serve`; this module
keeps the seed's LM inference driver, CLI unchanged.)
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, get_smoke
from repro.distributed.train import make_serve_fns
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import build_model
from repro.models.common import init_params


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model-par", type=int, default=1)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    model = build_model(cfg)
    mesh = make_local_mesh(data=args.data, model=args.model_par)
    prefill_fn, decode_fn, policy, param_sh = make_serve_fns(model, mesh)

    max_len = args.prompt_len + args.gen
    rng = np.random.default_rng(0)
    with mesh:
        params = init_params(model.param_specs(), jax.random.PRNGKey(0))
        params = jax.device_put(params, param_sh)
        batch = {
            "tokens": jnp.asarray(
                rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
                jnp.int32,
            )
        }
        if cfg.family == "vlm":
            batch["patch_embeds"] = jnp.zeros(
                (args.batch, cfg.vision_prefix, cfg.d_model), jnp.bfloat16
            )
        if cfg.family == "audio":
            batch["frames"] = jnp.zeros(
                (args.batch, cfg.encoder_seq, cfg.d_model), jnp.bfloat16
            )
        t0 = time.time()
        logits, cache = prefill_fn(params, batch, max_len)
        logits.block_until_ready()
        t_prefill = time.time() - t0

        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        outs = [np.asarray(tok)]
        t0 = time.time()
        for i in range(args.gen - 1):
            pos = jnp.int32(args.prompt_len + i)
            logits, cache = decode_fn(params, cache, tok, pos)
            tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            outs.append(np.asarray(tok))
        jax.block_until_ready(tok)
        t_decode = time.time() - t0

    gen = np.concatenate(outs, axis=1)
    print(f"prefill: {t_prefill*1e3:.1f} ms "
          f"({args.batch * args.prompt_len / t_prefill:.0f} tok/s)")
    print(f"decode:  {t_decode*1e3:.1f} ms "
          f"({args.batch * (args.gen - 1) / max(t_decode, 1e-9):.0f} tok/s)")
    print("sample generations (first 12 token ids):")
    for row in gen[:4]:
        print("  ", row[:12].tolist())


if __name__ == "__main__":
    main()
