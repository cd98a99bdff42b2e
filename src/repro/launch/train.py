"""End-to-end training driver.

Runs a real training loop on the local device(s): builds the model from
``--arch`` (reduced ``--smoke`` config by default on CPU), streams
deterministic token batches, checkpoints every ``--ckpt-every`` steps
(atomic, restartable), and resumes automatically from the newest checkpoint
— kill it mid-run and relaunch to exercise the fault-tolerance path.

  PYTHONPATH=src python -m repro.launch.train --arch granite-8b --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, get_smoke
from repro.data.pipeline import TokenPipeline
from repro.distributed import checkpoint as ckpt
from repro.distributed.compression import CompressionConfig
from repro.distributed.elastic import StepTimer
from repro.distributed.optimizer import AdamW, AdamWConfig
from repro.distributed.train import make_train_step
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import build_model
from repro.models.common import init_params


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--ckpt-compress", action="store_true",
                    help="FPTC-compress checkpoint leaves")
    ap.add_argument("--compression", default="none",
                    choices=["none", "truncate", "truncate_int8"])
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    model = build_model(cfg)
    mesh = make_local_mesh(data=args.data, model=args.model_par)
    opt = AdamW(AdamWConfig(base_lr=args.lr, warmup=10,
                            total_steps=args.steps))
    ts = make_train_step(
        model, opt, mesh,
        compression=CompressionConfig(mode=args.compression),
    )

    pipe = TokenPipeline(
        vocab_size=cfg.vocab_size, batch_size=args.batch, seq_len=args.seq
    )

    with mesh:
        params = init_params(model.param_specs(), jax.random.PRNGKey(0))
        params = jax.device_put(params, ts.param_shardings)
        opt_state = opt.init(params, with_residual=ts.compressor is not None)
        start_step = 0
        if args.ckpt_dir:
            restored = ckpt.restore_latest(
                args.ckpt_dir, {"params": params, "m": opt_state.m,
                                "v": opt_state.v}
            )
            if restored is not None:
                start_step, tree = restored
                params = jax.device_put(tree["params"], ts.param_shardings)
                opt_state = opt_state._replace(
                    m=tree["m"], v=tree["v"],
                    step=jnp.asarray(start_step, jnp.int32),
                )
                print(f"resumed from step {start_step}")

        timer = StepTimer()
        for step in range(start_step, args.steps):
            tokens, labels = pipe.batch(step)
            batch = {
                "tokens": jnp.asarray(tokens),
                "labels": jnp.asarray(labels),
            }
            if cfg.family == "vlm":
                batch["patch_embeds"] = jnp.zeros(
                    (args.batch, cfg.vision_prefix, cfg.d_model), jnp.bfloat16
                )
            if cfg.family == "audio":
                batch["frames"] = jnp.zeros(
                    (args.batch, cfg.encoder_seq, cfg.d_model), jnp.bfloat16
                )
            timer.start()
            params, opt_state, metrics = ts.step_fn(params, opt_state, batch)
            loss = float(metrics["loss"])
            dt, straggler = timer.stop()
            if step % args.log_every == 0 or step == args.steps - 1:
                print(
                    f"step {step:5d} loss {loss:8.4f} "
                    f"gnorm {float(metrics['grad_norm']):8.3f} "
                    f"{dt*1e3:7.1f} ms" + ("  [straggler]" if straggler else "")
                , flush=True)
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                host = jax.tree_util.tree_map(np.asarray, {
                    "params": params, "m": opt_state.m, "v": opt_state.v,
                })
                path = ckpt.save_checkpoint(
                    args.ckpt_dir, step + 1, host,
                    compress=args.ckpt_compress,
                )
                print(f"checkpointed -> {path}", flush=True)
    print("training done.")


if __name__ == "__main__":
    main()
