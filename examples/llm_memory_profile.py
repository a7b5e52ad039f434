"""The paper's thesis applied to our own workloads: profile an LLM step's
DRAM behaviour with MemorySim.

Takes an assigned architecture, derives its per-device decode-step HBM
traffic (weights + KV cache from the analytic model), synthesizes the DRAM
access stream, and runs it through BOTH the RTL-level simulator and the
ideal reference — reporting the effective-bandwidth efficiency that
refines the roofline memory term (EXPERIMENTS.md §Perf-beyond).

  PYTHONPATH=src python examples/llm_memory_profile.py --arch qwen2-72b
"""

import argparse

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.core import MemSimConfig
from repro.perfmodel.analytic import cell_cost, param_counts, HBM_BW
from repro.perfmodel.effective_bw import decode_efficiency


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-72b")
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--queue-size", type=int, default=128)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    pc = param_counts(cfg)
    cost = cell_cost(cfg, args.shape)
    params_dev = pc["total"] * 2 / 256          # bf16 shards on 256 chips
    kv_dev = cost.kv_bytes

    print(f"[profile] {cfg.name} x {args.shape}: "
          f"{pc['total']/1e9:.1f}B params ({pc['active']/1e9:.1f}B active)")
    print(f"[profile] per-device traffic: weights {params_dev/1e9:.2f} GB, "
          f"KV/state {kv_dev/1e9:.2f} GB per step")

    r = decode_efficiency(cfg.name, params_dev, kv_dev,
                          cfg=MemSimConfig(queue_size=args.queue_size))
    naive_t = cost.hbm_bytes / HBM_BW
    effective_t = naive_t / max(r.efficiency, 1e-6)
    print(f"[profile] MemorySim: {r.requests} requests "
          f"({r.bytes_per_request:.0f} B/request), "
          f"read latency {r.read_latency_mean:.0f} cycles, "
          f"refresh share {r.refresh_share:.1%}")
    print(f"[profile] effective bandwidth = {r.efficiency:.1%} of peak")
    print(f"[profile] memory roofline term: {naive_t*1e3:.2f} ms (peak BW) "
          f"-> {effective_t*1e3:.2f} ms (memsim-refined)")
    print("[profile] (the paper's pitch, closed-loop: behavioural rooflines "
          "overstate achievable bandwidth; the RTL model quantifies by how much)")


if __name__ == "__main__":
    main()
