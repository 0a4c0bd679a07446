"""LM training driver for the assigned architectures.

    PYTHONPATH=src python examples/train_lm.py --arch smollm-135m --smoke \\
        --steps 30                       # reduced config, CPU
    PYTHONPATH=src python examples/train_lm.py --arch llama3-8b   # full (TPU)

Any of the 10 assigned archs is selectable; --smoke swaps in the reduced
same-family config so the full loop (data -> sharded train step -> ckpt ->
resume) runs on this CPU container. The full configs are exercised by the
multi-pod dry-run (launch/dryrun.py).
"""

import argparse

import jax
import jax.numpy as jnp

from repro import models as M
from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.data.pipeline import DataConfig, TokenStream
from repro.optim import AdamWConfig, init_opt_state
from repro.train.train_step import make_train_step
from repro.train.trainer import Trainer, TrainerConfig
from repro.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    args = ap.parse_args()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"{args.arch}{' (smoke)' if args.smoke else ''}: {n / 1e6:.1f}M params")

    opt = AdamWConfig(lr=1e-3)
    opt_state = init_opt_state(params, opt)
    step = jax.jit(make_train_step(
        cfg, M.DEFAULT_PLAN, opt,
        compute_dtype=jnp.float32 if args.smoke else jnp.bfloat16,
    ))
    stream = TokenStream(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch
    ))

    def data_fn(s):
        b = {"tokens": jnp.asarray(stream.batch(s)["tokens"])}
        if cfg.is_vlm:
            b["image_embeds"] = jnp.zeros((args.batch, cfg.n_image_tokens, 1024))
        if cfg.is_encoder_decoder:
            b["frames"] = jnp.zeros((args.batch, cfg.n_encoder_frames, cfg.d_model))
        return b

    trainer = Trainer(step, data_fn, TrainerConfig(
        total_steps=args.steps, ckpt_every=10, ckpt_dir=args.ckpt_dir, log_every=5,
    ))
    _, _, history = trainer.run(params, opt_state)
    for h in history:
        print(f"step {h['step']:4d}  loss {h['loss']:.4f}  {h['dt'] * 1e3:.0f} ms")
    print("first->last logged loss: "
          f"{history[0]['loss']:.3f} -> {history[-1]['loss']:.3f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
