"""End-to-end training driver.

Single-process (CPU here, same code under a real mesh): builds the model
from ``--arch``, the synthetic data pipeline, AdamW + schedule, wraps the
jitted train step in the fault-tolerant Supervisor (checkpoint-restart,
straggler watchdog) and runs ``--steps`` steps.

    PYTHONPATH=src python -m repro.launch.train \
        --arch minicpm-2b --smoke --steps 50 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_config
from repro.configs.archs import smoke_config
from repro.data.pipeline import make_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as mdl
from repro.models import params as pm
from repro.models.transformer import model_spec
from repro.optim import adamw_init, adamw_update, cosine, wsd
from repro.runtime import FailureInjector, Supervisor, TrainLoopConfig


def make_step(cfg, schedule, *, overlay=None):
    """The jitted train step; with ``overlay`` it is JIT-assembled instead:
    traced by the overlay frontend, lowered onto the operator library (grad
    and optimizer primitives stay fused XLA residue) and cached as a
    bitstream — same numerics, same donation, paper-C1 programming model."""
    def train_step(state, batch):
        params, opt_state = state
        (loss, metrics), grads = jax.value_and_grad(
            mdl.loss_fn, has_aux=True)(params, batch, cfg)
        lr = schedule(opt_state.step)
        params, opt_state, om = adamw_update(params, grads, opt_state, lr=lr)
        return (params, opt_state), {"loss": loss, "lr": lr, **metrics, **om}
    if overlay is not None:
        return overlay.jit(train_step, strict=False,
                           name=f"{cfg.name}.train_step",
                           donate_argnums=(0,))
    return jax.jit(train_step, donate_argnums=(0,))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--schedule", choices=["cosine", "wsd"], default="cosine")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject simulated node failures at these steps")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--assemble-overlay", action="store_true",
                    help="run the train step through the overlay JIT-assembly "
                         "frontend instead of a bare jax.jit")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    spec = model_spec(cfg)
    print(f"[train] {cfg.name}: {pm.count(spec)/1e6:.2f}M params, "
          f"{cfg.num_layers} layers")

    params = pm.init(spec, jax.random.PRNGKey(args.seed))
    opt_state = adamw_init(params)

    if args.schedule == "wsd":
        schedule = wsd(args.lr, warmup=max(args.steps // 20, 1),
                       stable=args.steps * 7 // 10,
                       decay=max(args.steps // 5, 1))
    else:
        schedule = cosine(args.lr, warmup=max(args.steps // 20, 1),
                          total=args.steps)

    overlay = None
    if args.assemble_overlay:
        from repro.core import Overlay
        overlay = Overlay(3, 3)
    step_fn = make_step(cfg, schedule, overlay=overlay)

    def batch_fn(step: int) -> dict:
        return make_batch(cfg, args.batch, args.seq, step=step,
                          seed=args.seed)

    losses = []

    def logged_step(state, batch):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        n = len(losses)
        if n % args.log_every == 0 or n == 1:
            print(f"  step {n:5d}  loss {losses[-1]:.4f}  "
                  f"lr {float(metrics['lr']):.2e}  "
                  f"gnorm {float(metrics['grad_norm']):.2f}")
        return state, metrics

    sup = Supervisor(
        TrainLoopConfig(total_steps=args.steps, ckpt_every=args.ckpt_every),
        args.ckpt_dir,
        injector=FailureInjector(fail_at=tuple(args.fail_at)))

    t0 = time.perf_counter()
    state = sup.run((params, opt_state), logged_step, batch_fn)
    dt = time.perf_counter() - t0
    print(f"[train] done: {args.steps} steps in {dt:.1f}s "
          f"({dt/max(args.steps,1)*1000:.0f} ms/step), "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}, "
          f"restarts={sup.restarts} stragglers={sup.straggler_steps}")
    if overlay is not None:
        print(f"[train] overlay: {overlay.describe()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
