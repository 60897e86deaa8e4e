"""End-to-end training driver (deliverable b).

Runs real optimisation steps — on this CPU container with a reduced
config ("--smoke", default) or on a real mesh with the full config.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --steps 50
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --no-smoke \
      --mesh 16x16        # on hardware
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.serialize import save
from repro.configs import get_config, get_smoke_config
from repro.data.synthetic import lm_token_batches
from repro.models.config import InputShape
from repro.models.zoo import get_model
from repro.optim import adamw, cosine_schedule, sgd
from repro.utils.compile_cache import use_compile_cache


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "sgd"])
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    cfg = cfg.replace(microbatches=1)
    model = get_model(cfg)
    print(f"arch={cfg.name} params≈{cfg.n_params()/1e6:.1f}M "
          f"(smoke={args.smoke})")

    params = model.init_params(jax.random.PRNGKey(args.seed))
    if args.optimizer == "adamw":
        opt = adamw(cosine_schedule(args.lr, warmup=20, total=args.steps))
    else:
        opt = sgd(args.lr, momentum=0.5)
    opt_state = opt.init(params)
    train_step = jax.jit(model.make_train_step(opt))

    shape = InputShape("cli", args.seq, args.batch, "train")
    gen = lm_token_batches(cfg.vocab, args.batch, args.seq,
                           args.steps, seed=args.seed)
    t0 = time.time()
    losses = []
    for step, batch in enumerate(gen):
        if cfg.family == "vlm":
            batch["patch_embeds"] = jnp.zeros(
                (args.batch, cfg.vlm.n_patches, cfg.vlm.d_vision),
                cfg.cdtype)
        if cfg.family == "encdec":
            batch = {
                "audio_embeds": jnp.zeros(
                    (args.batch, cfg.encdec.enc_seq, cfg.d_model),
                    cfg.cdtype),
                "tokens": batch["tokens"][:, :cfg.encdec.dec_seq],
                "labels": batch["labels"][:, :cfg.encdec.dec_seq],
            }
        params, opt_state, loss = train_step(params, opt_state, batch,
                                             jnp.int32(step))
        losses.append(float(loss))
        if step % args.log_every == 0:
            dt = time.time() - t0
            print(f"step {step:5d} loss {float(loss):7.4f} "
                  f"({dt / (step + 1):5.2f}s/step)")
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f}) — "
          f"{'improved' if losses[-1] < losses[0] else 'NO IMPROVEMENT'}")
    if args.checkpoint:
        save(args.checkpoint, {"params": params, "losses": losses})
        print(f"saved {args.checkpoint}")


if __name__ == "__main__":
    main()
