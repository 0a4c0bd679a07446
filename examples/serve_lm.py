"""Batched LM serving: prefill a prompt batch, then greedy/temperature
decode with the KV cache (bf16 or int8).

    PYTHONPATH=src python examples/serve_lm.py --arch smollm-135m --smoke \\
        --prompt-len 32 --gen 32 --cache int8
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro import models as M
from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.serve.serve_step import make_decode_step, make_prefill_step
from repro.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--cache", default="bfloat16", choices=["bfloat16", "int8", "float32"])
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    max_len = args.prompt_len + args.gen
    cache_dtype = {"bfloat16": jnp.bfloat16, "int8": jnp.int8,
                   "float32": jnp.float32}[args.cache]

    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab
    )
    batch = {"tokens": prompts}
    if cfg.is_encoder_decoder:
        batch["frames"] = jnp.zeros((args.batch, cfg.n_encoder_frames, cfg.d_model))
    if cfg.is_vlm:
        batch["image_embeds"] = jnp.zeros((args.batch, cfg.n_image_tokens, 1024))

    state = M.init_decode_state(cfg, M.DEFAULT_PLAN, args.batch, max_len,
                                cache_dtype=cache_dtype)
    prefill = jax.jit(make_prefill_step(cfg, M.DEFAULT_PLAN))
    decode = jax.jit(make_decode_step(cfg, M.DEFAULT_PLAN, args.temperature))

    t0 = time.time()
    logits, state = prefill(params, batch, state)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t_prefill = time.time() - t0

    out_tokens = [nxt]
    rng = jax.random.PRNGKey(2)
    t0 = time.time()
    for i in range(args.gen - 1):
        rng, sub = jax.random.split(rng)
        pos = jnp.int32(args.prompt_len + i)
        nxt, logits, state = decode(params, state, nxt, pos, sub)
        out_tokens.append(nxt)
    jax.block_until_ready(nxt)
    t_dec = time.time() - t0

    gen = jnp.stack(out_tokens, axis=1)
    print(f"{args.arch} ({'smoke' if args.smoke else 'full'}), cache={args.cache}")
    print(f"prefill {args.batch}x{args.prompt_len}: {t_prefill * 1e3:.0f} ms")
    print(f"decode  {args.gen - 1} steps: {t_dec * 1e3:.0f} ms "
          f"({args.batch * (args.gen - 1) / max(t_dec, 1e-9):.0f} tok/s, CPU)")
    print("sample:", gen[0, :16].tolist())


if __name__ == "__main__":
    enable_compile_cache()
    main()
