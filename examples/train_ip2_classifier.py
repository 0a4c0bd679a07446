"""End-to-end driver: co-design training of the IP2 analog front-end with a
patch-token transformer backend (the paper's classification study, §1).

    PYTHONPATH=src python examples/train_ip2_classifier.py --preset cpu-small
    PYTHONPATH=src python examples/train_ip2_classifier.py --preset 100m \\
        --steps 300        # ~100M-param backend; sized for real hardware

Trains the in-pixel weight matrix A jointly with the backend through the
STE-quantized analog path, with fault-tolerant checkpointing (kill and
rerun: it resumes from the last commit).
"""

import argparse

import jax
import jax.numpy as jnp

import repro.optim as O
from repro.core.frontend import FrontendConfig
from repro.core.projection import PatchSpec
from repro.data.pipeline import SceneStream
from repro.models.vit import ViTConfig, init_vit, vit_loss
from repro.train.trainer import Trainer, TrainerConfig
from repro.compile_cache import enable_compile_cache

PRESETS = {
    # ~0.5M backend: trains to high accuracy on CPU in ~2 min
    "cpu-small": dict(image=64, patch=16, n_vectors=32, n_layers=2,
                      d_model=64, n_heads=4, d_ff=128, batch=32),
    # ~100M backend at the paper's 32x32/400-vector design point (for TPU)
    "100m": dict(image=256, patch=32, n_vectors=400, n_layers=12,
                 d_model=768, n_heads=12, d_ff=3072, batch=64),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="cpu-small", choices=PRESETS)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--active", type=float, default=0.25)
    ap.add_argument("--ckpt-dir", default="/tmp/ip2_classifier_ckpt")
    args = ap.parse_args()
    p = PRESETS[args.preset]

    cfg = ViTConfig(
        frontend=FrontendConfig(
            image_h=p["image"], image_w=p["image"],
            patch=PatchSpec(patch_h=p["patch"], patch_w=p["patch"],
                            n_vectors=p["n_vectors"]),
            active_fraction=args.active,
        ),
        n_classes=4, n_layers=p["n_layers"], d_model=p["d_model"],
        n_heads=p["n_heads"], d_ff=p["d_ff"],
    )
    params = init_vit(jax.random.PRNGKey(0), cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"preset={args.preset}: {n_params / 1e6:.1f}M params, "
          f"{cfg.frontend.n_patches} patches, {args.active:.0%} active")

    opt = O.AdamWConfig(lr=2e-3, weight_decay=0.01)
    opt_state = O.init_opt_state(params, opt)
    stream = SceneStream(image=p["image"])

    @jax.jit
    def train_step(params, opt_state, batch):
        (loss, acc), g = jax.value_and_grad(vit_loss, has_aux=True)(
            params, batch["rgb"], batch["labels"], cfg
        )
        params, opt_state, m = O.adamw_update(
            g, opt_state, params, opt, jnp.float32(opt.lr)
        )
        return params, opt_state, {"loss": loss, "acc": acc, **m}

    def data_fn(step):
        rgb, labels = stream.batch(step, p["batch"])
        return {"rgb": jnp.asarray(rgb), "labels": jnp.asarray(labels)}

    trainer = Trainer(
        train_step, data_fn,
        TrainerConfig(total_steps=args.steps, ckpt_every=50,
                      ckpt_dir=args.ckpt_dir, log_every=20),
    )
    params, opt_state, history = trainer.run(params, opt_state)
    for h in history:
        print(f"step {h['step']:4d}  loss {h['loss']:.3f}  {h['dt'] * 1e3:.0f} ms")

    # held-out eval
    accs = []
    for j in range(8):
        rgb, labels = stream.batch(10_000 + j, p["batch"])
        _, acc = vit_loss(params, jnp.asarray(rgb), jnp.asarray(labels), cfg)
        accs.append(float(acc))
    print(f"held-out accuracy: {sum(accs) / len(accs):.3f} "
          f"(stragglers observed: {trainer.n_stragglers})")


if __name__ == "__main__":
    enable_compile_cache()
    main()
