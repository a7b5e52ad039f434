"""Token-by-token decode serving trace: a copy of
``repro.traces.llm_workload.decode_serving_trace`` that returns arrays.

Every job of a cell serves the same requests: the trace is drawn once from
``params["draw_seed"]``, and a job's seed only reorders the KV gathers
inside each token's burst. Arrival times, sizes and the set of addresses
stay the same, so the seed does not change how much work a job is, and
still changes which request meets which bank state."""

from __future__ import annotations

import numpy as np


def generate(params: dict, seed: int):
    """``(t, addr, is_write, wdata)`` of one job."""
    kw = {k: v for k, v in params.items() if k != "draw_seed"}
    t, addr, is_write, wdata = decode_serving_trace(
        **kw, seed=params["draw_seed"])
    n_kv = max(1, int(kw["reads_per_token"] * kw["kv_frac"]))
    n_w = kw["reads_per_token"] - n_kv
    per_token = kw["reads_per_token"] + 1
    rng = np.random.default_rng(seed)
    kv = addr.reshape(-1, per_token)[:, n_w:n_w + n_kv]
    kv[...] = rng.permuted(kv, axis=1)
    return t, addr, is_write, wdata


def decode_serving_trace(tokens: int = 96, reads_per_token: int = 16,
                         compute_gap: int = 4000, kv_frac: float = 0.25,
                         seed: int = 0):
    """Token-by-token decode serving stream — the WAIT-heavy regime.

    Each generated token triggers a burst of weight-shard and KV-cache
    reads (one per cycle, striped across banks), then the memory port goes
    quiet for ``compute_gap`` cycles while the accelerator does the matmul.
    During the burst drain the banks sit in *staggered* ACT/RW/PRE WAIT
    states and blocked column bids — exactly the phase the event-horizon
    engine collapses to its event count and a drained-gate engine cannot.

    Weight reads walk sequential rows (a fresh region per token — decode
    re-streams every shard); KV reads gather from a growing cache region.
    Returns ``(t, addr, is_write, wdata)`` int32 arrays, sorted by ``t``.
    """
    rng = np.random.default_rng(seed)
    w_base, k_base = 0, 1 << 24
    times, addrs, writes = [], [], []
    t = 0
    n_kv = max(1, int(reads_per_token * kv_frac))
    n_w = reads_per_token - n_kv
    for tok in range(tokens):
        # unit stride: consecutive words stripe across banks/bankgroups
        # (the {bank, bankgroup, rank} bits are the address LSBs), the way
        # a weight shard's DMA burst fans out over the whole device
        w_start = (tok * n_w) % (1 << 23)
        for i in range(n_w):
            times.append(t)
            addrs.append(w_base + w_start + i)
            writes.append(0)
            t += 1
        for i in range(n_kv):
            times.append(t)
            addrs.append(k_base + int(rng.integers(0, (tok + 1) * 512)))
            writes.append(0)
            t += 1
        # KV append for the new token
        times.append(t)
        addrs.append(k_base + (tok + 1) * 512)
        writes.append(1)
        t += compute_gap
    n = len(times)
    return (np.asarray(times, np.int64).astype(np.int32),
            (np.asarray(addrs, np.int64) & 0x3FFFFFFF).astype(np.int32),
            np.asarray(writes, np.int32),
            (np.arange(n, dtype=np.int64) & 0x7FFFFFFF).astype(np.int32))
