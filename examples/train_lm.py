"""End-to-end driver: train a ~100M-param qwen3-family model for a few
hundred steps on CPU, with checkpointing and WSD/cosine scheduling.

This is the assignment's end-to-end example: a REAL (reduced-width, same
family) model through the full production path — synthetic sharded data
pipeline, microbatched train step, async checkpointing — and the loss must
actually go down.

  PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.store import CheckpointStore
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.launch.steps import make_train_step
from repro.models import registry
from repro.optim import AdamWConfig, adamw_init, schedules


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    # ~100M params: qwen3 family at reduced width/depth
    cfg = dataclasses.replace(
        get_config("qwen3-14b"),
        name="qwen3-100m", n_layers=8, d_model=512, n_heads=8, n_kv_heads=4,
        d_head=64, d_ff=2048, vocab=32768, remat="none", loss_chunk=128,
        max_seq=4096,
    ).validate()

    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"[train_lm] {cfg.name}: {n/1e6:.1f}M params, "
          f"{args.steps} steps @ batch {args.batch} x seq {args.seq}")

    schedule = schedules.make("cosine", 3e-4, args.steps, warmup=20)
    step_fn = jax.jit(make_train_step(cfg, schedule=schedule,
                                      opt_cfg=AdamWConfig(weight_decay=0.01),
                                      dtype=jnp.float32, num_microbatches=2),
                      donate_argnums=(0, 1))
    opt = adamw_init(params)
    store = CheckpointStore(args.ckpt_dir)
    data = Prefetcher(SyntheticLM(cfg, args.batch, args.seq, seed=0))

    first_loss = None
    t0 = time.time()
    try:
        for step, batch in data:
            if step >= args.steps:
                break
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            params, opt, m = step_fn(params, opt, batch)
            if first_loss is None:
                first_loss = float(m["loss"])
            if step % 25 == 0 or step == args.steps - 1:
                tps = (step + 1) * args.batch * args.seq / (time.time() - t0)
                print(f"  step {step:4d} loss={float(m['loss']):.4f} "
                      f"lr={float(m['lr']):.2e} tok/s={tps:.0f}")
            if (step + 1) % 100 == 0:
                store.save_async(step + 1, params, opt)
    finally:
        data.close()
        store.wait()

    final_loss = float(m["loss"])
    print(f"[train_lm] loss {first_loss:.3f} -> {final_loss:.3f} "
          f"in {time.time()-t0:.0f}s; checkpoint at {args.ckpt_dir}")
    assert final_loss < first_loss, "training must reduce the loss"


if __name__ == "__main__":
    main()
