"""End-to-end LM training driver with production plumbing:

  * deterministic seekable data pipeline,
  * atomic/async checkpointing + exact resume,
  * straggler watchdog (p99 step-time flagging),
  * optional int8 error-feedback gradient compression,
  * optional simulated mid-run failure (--simulate-failure) to exercise
    the recovery path.

Default config is a ~20M-param llama-style model that trains a few
hundred steps on CPU; --preset 100m gives the ~100M assignment target.

Run:  PYTHONPATH=src python examples/train_lm.py --steps 200
"""
import argparse
import pathlib
import time

import jax
import numpy as np

from repro.ckpt.checkpoint import CheckpointManager
from repro.configs.base import LayerSpec, ModelConfig
from repro.data.pipeline import TokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.train import trainer
from repro.train.compression import ef_compress, init_residual, wire_bytes

PRESETS = {
    "20m": dict(n_layers=4, d_model=256, n_heads=8, n_kv_heads=4,
                head_dim=32, d_ff=1024, vocab_size=8192),
    "100m": dict(n_layers=8, d_model=512, n_heads=8, n_kv_heads=8,
                 head_dim=64, d_ff=2048, vocab_size=32768),
}


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="20m", choices=list(PRESETS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ckpt-dir", default="artifacts/train_lm_ckpt")
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--simulate-failure", action="store_true",
                    help="crash at step 60%% through; rerun to resume")
    args = ap.parse_args()

    cfg = ModelConfig(name=f"lm-{args.preset}", pattern=(LayerSpec(),),
                      **PRESETS[args.preset])
    n = cfg.param_counts()["total"]
    print(f"model {cfg.name}: {n/1e6:.1f}M params")

    pipe = TokenPipeline(cfg.vocab_size, args.seq, args.batch, seed=7)
    state = trainer.make_train_state(jax.random.PRNGKey(0), cfg)
    mgr = CheckpointManager(args.ckpt_dir, keep=2)
    start = 0
    if mgr.latest_step() is not None:
        state, start = mgr.restore(state)
        print(f"resumed from checkpoint at step {start}")

    residual = init_residual(state["params"]) if args.grad_compress else None
    compress = None
    if args.grad_compress:
        un, comp = wire_bytes(state["params"])
        print(f"grad compression: {un/1e6:.1f}MB -> {comp/1e6:.1f}MB on the "
              f"cross-pod wire per step")

        def compress(grads):
            nonlocal residual
            g, residual = ef_compress(grads, residual)
            return g

    @jax.jit
    def step_fn(state, batch):
        return trainer.train_step(cfg, state, batch,
                                  grad_compress=compress)

    times = []
    fail_at = int(args.steps * 0.6)
    for step in range(start, args.steps):
        t0 = time.perf_counter()
        batch = jax.tree.map(jax.numpy.asarray, pipe.batch_at(step))
        state, metrics = step_fn(state, batch)
        jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        times.append(dt)
        # straggler watchdog: flag steps beyond p99 of the trailing window
        if len(times) > 20:
            p99 = float(np.percentile(times[-50:], 99))
            if dt > max(2 * np.median(times[-50:]), p99 * 1.5):
                print(f"  [watchdog] step {step} took {dt*1e3:.0f}ms "
                      f"(p99 {p99*1e3:.0f}ms) — straggler flagged")
        if step % 20 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss {float(metrics['loss']):.4f} "
                  f"lr {float(metrics['lr']):.2e} {dt*1e3:.0f}ms", flush=True)
        if step and step % args.ckpt_every == 0:
            mgr.save(step, state)
        if args.simulate_failure and step == fail_at and start == 0:
            mgr.save(step, state)
            mgr.wait()
            print(f"simulated failure at step {step} — rerun to resume")
            raise SystemExit(17)
    mgr.save(args.steps, state)
    mgr.wait()
    print(f"done; median step {np.median(times)*1e3:.0f}ms; "
          f"checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    main()
