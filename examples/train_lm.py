"""End-to-end LM training driver: any assigned architecture (reduced or
full), synthetic k-gram token stream, AdamW + cosine schedule, atomic
checkpoints with exact resume, straggler watchdog.

    PYTHONPATH=src python examples/train_lm.py --arch qwen2_0_5b \
        --steps 60 --batch 8 --seq 128
    # kill it mid-run and re-run: it resumes from the latest checkpoint
    # (--ckpt sets the checkpoint dir, default results/train_lm_ckpt)

    --full uses the exact assigned config (for real hardware; the smoke
    config is the CPU default).
"""

import argparse

import jax
import jax.numpy as jnp

from repro import configs as C
from repro.compile_cache import enable_compile_cache
from repro.data import make_token_stream
from repro.models.steps import make_train_step
from repro.models.transformer import init_params
from repro.optim import adamw, linear_warmup_cosine
from repro.runtime import LoopConfig, TrainLoop


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_0_5b")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--ckpt", default="results/train_lm_ckpt")
    args = ap.parse_args()

    cfg = C.get(args.arch) if args.full else C.get_smoke(args.arch)
    print(f"arch={cfg.name}  params={cfg.n_params()/1e6:.1f}M")
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw(linear_warmup_cosine(3e-3, 10, args.steps))
    opt_state = opt.init(params)
    raw_step = make_train_step(cfg, opt)
    sample = make_token_stream(0, cfg.vocab)

    @jax.jit
    def step_fn(state, batch):
        params, opt_state = state
        params, opt_state, metrics = raw_step(params, opt_state, batch)
        return (params, opt_state), metrics

    def batch_fn(step):
        toks = sample(step, args.batch, args.seq)
        batch = {"tokens": toks, "labels": toks}
        if cfg.n_frontend_embeds:
            batch["frontend_embeds"] = jnp.zeros(
                (args.batch, cfg.n_frontend_embeds, cfg.d_model),
                cfg.dtype,
            )
        return batch

    loop = TrainLoop(
        step_fn, batch_fn, (params, opt_state),
        LoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt,
                   save_every=20, async_save=True),
        on_straggler=lambda s, dt: print(f"  [watchdog] slow step {s}: {dt:.2f}s"),
    )
    resumed = loop.restore_if_available()
    if resumed:
        print(f"resumed from checkpoint at step {resumed}")
    out = loop.run()
    first = out["metrics"][0] if out["metrics"] else {}
    last = out["metrics"][-1] if out["metrics"] else {}
    print(
        f"steps {loop.start_step}->{out['final_step']}  "
        f"loss {first.get('loss', float('nan')):.3f} -> "
        f"{last.get('loss', float('nan')):.3f}"
    )


if __name__ == "__main__":
    main()
