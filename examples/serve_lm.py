"""Serving example: batched greedy decode with prefill->decode equivalence.

Demonstrates the serving path the decode_32k / long_500k dry-run cells
lower: batch prefill to seed KV caches, then batched one-token steps, with
a throughput report and an assertion that incremental decode reproduces
teacher-forced logits (the system's core serving invariant).

  PYTHONPATH=src python examples/serve_lm.py --arch jamba-v0.1-52b
"""

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.models import lm, registry
from repro.models.layers import rmsnorm


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=40)
    args = ap.parse_args()

    cfg = get_config(args.arch).tiny()
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    b = args.batch
    max_seq = args.prompt_len + args.max_new

    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(1, cfg.vocab, size=(b, args.prompt_len)), jnp.int32)

    # ---- teacher-forced reference logits over the prompt -----------------
    x, _, _ = lm.forward(cfg, params, prompts)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    head = (params["embed"]["table"].T if cfg.tie_embeddings
            else params["lm_head"])
    ref_last = (x[:, -1] @ head).astype(jnp.float32)

    # ---- incremental decode over the same prompt + generation ------------
    decode = jax.jit(functools.partial(lm.decode_step, cfg))
    caches = lm.init_caches(cfg, b, max_seq)
    t0 = time.time()
    for t in range(args.prompt_len):
        pos = jnp.full((b,), t, jnp.int32)
        logits, caches = decode(params, caches, prompts[:, t], pos)
    err = float(jnp.abs(logits - ref_last).max())
    print(f"[serve_lm] prefill-vs-decode max logit err: {err:.2e}")
    assert err < 5e-2, "incremental decode diverged from teacher forcing"

    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    generated = [np.asarray(tok)]
    for t in range(args.prompt_len, max_seq - 1):
        pos = jnp.full((b,), t, jnp.int32)
        logits, caches = decode(params, caches, tok, pos)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        generated.append(np.asarray(tok))
    dt = time.time() - t0
    total = b * (max_seq - 1)
    print(f"[serve_lm] {args.arch}: {b} seqs x {max_seq-1} steps "
          f"-> {total/dt:.0f} tok/s (tiny config, CPU)")
    out = np.stack(generated, 1)
    print(f"[serve_lm] sample: {out[0][:12].tolist()}")


if __name__ == "__main__":
    main()
