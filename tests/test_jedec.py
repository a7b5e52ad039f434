"""The ``"jedec"`` timing model: bank-group (S/L) windows and a per-bank
precharge gate.

Every engine path — ``simulate_fast``, a ``sweep_grid`` whose lanes mix
page policy, scheduler and the L gaps, and ``SessionBatch`` windows, on
the ``jnp`` and ``fused`` backends — is held to the per-cycle
``simulate`` record for record, word for word and counter for counter
(the grant counters included), and the two backends' event horizons to
each other step for step. The commands the per-cycle circuit issues
are checked by the plain protocol checker
(:mod:`bench.reference_jedec.protocol`): none breaks a window, and a
circuit with one window left unenforced is caught on that window.

The topology is small: 2 channels x 1 rank x 8 bank groups x 2 banks,
queues of 16, with DDR5-like gaps scaled down so that refresh and self
refresh fall inside the horizon.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference_jedec import protocol
from repro.core import (
    MemSimConfig,
    SessionBatch,
    simulate,
    simulate_fast,
    sweep_grid,
)
from repro.core.simulator import Trace

TOPOLOGY = dict(channels=2, ranks=1, bankgroups=8, banks_per_group=2,
                queue_size=16, resp_queue_size=16, mem_words=4096,
                timing_model="jedec")
TIMINGS = dict(tCL=8, tRCDRD=8, tRCDWR=8, tRP=8, tRAS=16, tRTP=5, tWTP=24,
               tRRDS=4, tRRDL=6, tFAW=16, tCCDS=4, tCCDL=6, tWTRS=6,
               tWTR=10, tRTW=6, tREFI=700, tRFC=60, tXS=70,
               sref_idle_cycles=200)
HORIZON = 1600
RECORDS = ("t_admit", "t_dispatch", "t_start", "t_complete", "rdata")


def cfg(**kw) -> MemSimConfig:
    return MemSimConfig(**TOPOLOGY, **dict(TIMINGS, **kw))


def arrays(seed: int, bursts: int = 4):
    """Decode-like bursts, one request a cycle: unit-stride reads that
    walk the bank groups (consecutive addresses share a group in pairs),
    reads to one open row (32 words apart: same bank, row and group) and
    random reads and writes. Each burst ends, once the channel is quiet,
    with a few requests to the two banks of one group only — a write, a
    read that conflicts with its row, row hits — so that the group's L
    windows and the precharge gate hold commands whatever the arbiter
    does. Then a quiet gap long enough for self refresh."""
    rng = np.random.default_rng(seed)
    t, addr, write = [], [], []
    now = int(rng.integers(0, 20))
    for k in range(bursts):
        base = int(rng.integers(0, 64)) * 2048     # bank 0, group 0, ch 0
        burst = ([(base + i, 0) for i in range(12)]
                 + [(base + 16 + 32 * i, 0) for i in range(4)]
                 + [(int(rng.integers(0, 1 << 16)), int(rng.random() < 0.5))
                    for _ in range(6)])
        group = [(base + 2048 * 10, 1), (base + 2048 * 11, 0),
                 (base + 2048 * 12 + 1, 0), (base + 2048 * 12 + 33, 0),
                 (base + 2048 * 12 + 65, 0), (base + 2048 * 13, 0)]
        for i, (a, w) in enumerate(burst + group):
            if i == len(burst):
                now += 80
            t.append(now)
            addr.append(a)
            write.append(w)
            now += 1
        now += int(rng.integers(100, 400))
    n = len(t)
    return (np.asarray(t, np.int32), np.asarray(addr, np.int32),
            np.asarray(write, np.int32),
            rng.integers(0, 1 << 20, n).astype(np.int32))


def trace(seed: int) -> Trace:
    return Trace(*(jnp.asarray(a) for a in arrays(seed)))


def assert_same(ref, res, label=""):
    for f in RECORDS:
        np.testing.assert_array_equal(getattr(ref, f), getattr(res, f),
                                      err_msg=f"{label}: {f}")
    assert set(ref.counters) == set(res.counters), label
    for k in ref.counters:
        np.testing.assert_array_equal(np.asarray(ref.counters[k]),
                                      np.asarray(res.counters[k]),
                                      err_msg=f"{label}: counter {k}")
    assert ref.blocked_arrival == res.blocked_arrival, label
    assert ref.blocked_dispatch == res.blocked_dispatch, label


def _simulate_fast(page, sched, seed):
    """Both backends' skip engines against the per-cycle circuit, and
    against each other in the steps they execute: the kernel's event
    bound skips held bids exactly as far as the unfused one."""
    c = cfg(page_policy=page, sched_policy=sched)
    tr = trace(seed)
    ref = simulate(c, tr, HORIZON)
    steps = {}
    for backend in ("jnp", "fused"):
        tm = {}
        res = simulate_fast(dataclasses.replace(c, fsm_backend=backend), tr,
                            HORIZON, timings=tm)
        assert_same(ref, res, f"{backend} {page} {sched}")
        steps[backend] = tm["steps"]
    assert steps["jnp"] == steps["fused"] < HORIZON, steps
    # the windows, the bank group and the gate all held something
    assert ref.counters["grants_l_bound"] > 0
    assert ref.counters["pre_held"] > 0
    assert ref.counters["grants_windowed"] >= ref.counters["grants_l_bound"]


def _sweep_grid(backend, batch_mode):
    grid = {"page_policy": ["closed", "open"],
            "sched_policy": ["fcfs", "frfcfs"],
            "tCCDL": [6, 8], "tRRDL": [6, 8]}
    c = cfg(fsm_backend=backend)
    tr = trace(7)
    results = sweep_grid(c, tr, grid, HORIZON, batch_mode=batch_mode)
    assert len(results) == 16
    for res in results:
        point = dataclasses.replace(res.cfg, fsm_backend="jnp")
        assert_same(simulate(point, tr, HORIZON), res, repr(point)[:120])


def _session_batch(backend, batch_mode):
    c = cfg(fsm_backend=backend, page_policy="open", sched_policy="frfcfs")
    payloads = [arrays(s) for s in (11, 12, 13)]
    batch = SessionBatch.open(c, len(payloads), capacity=128,
                              batch_mode=batch_mode)
    for lane, payload in enumerate(payloads):
        batch.append(lane, payload)
    batch.run_until(HORIZON, 97)
    for lane, payload in enumerate(payloads):
        tr = Trace(*(jnp.asarray(a) for a in payload))
        ref = simulate(dataclasses.replace(c, fsm_backend="jnp"), tr,
                       HORIZON)
        assert_same(ref, batch.lane_result(lane), f"lane {lane}")


def _protocol_holds(page):
    """The per-cycle circuit's commands keep every window, and the
    reference copy issues the very same commands."""
    from bench.reference_jedec import params as ref_params
    from bench.reference_jedec import simulator as ref_sim

    c = cfg(page_policy=page)
    ref_cfg = ref_params.MemSimConfig(**TOPOLOGY, **TIMINGS,
                                      page_policy=page)
    for seed in (3, 4):
        a = arrays(seed)
        res, log = simulate(c, Trace(*(jnp.asarray(x) for x in a)),
                            HORIZON, log=True)
        ref, ref_log = ref_sim.simulate(
            ref_cfg, ref_sim.Trace(*(jnp.asarray(x) for x in a)), HORIZON,
            log=True)
        np.testing.assert_array_equal(log, ref_log)
        assert_same(ref, res, f"reference copy, seed {seed}")
        assert len(log) > 300
        bad = protocol.violations(log, TOPOLOGY, c.runtime()._asdict())
        assert set(bad) == set(protocol.WINDOWS)
        assert not any(bad.values()), bad


def _protocol_catches(window, broken):
    """A circuit with ``broken`` gaps, checked against the true ones, is
    caught on ``window`` and on nothing else."""
    c = cfg(page_policy="closed")
    caught = {}
    for seed in (3, 4):
        _, log = simulate(dataclasses.replace(c, **broken), trace(seed),
                          HORIZON, log=True)
        for k, v in protocol.violations(log, TOPOLOGY,
                                        c.runtime()._asdict()).items():
            caught[k] = caught.get(k, 0) + v
    assert {k for k, v in caught.items() if v} == {window}, caught


CASES = {
    "simulate_fast-closed-fcfs":
        lambda: _simulate_fast("closed", "fcfs", 1),
    "simulate_fast-open-frfcfs":
        lambda: _simulate_fast("open", "frfcfs", 2),
    "simulate_fast-closed-frfcfs":
        lambda: _simulate_fast("closed", "frfcfs", 3),
    "simulate_fast-open-fcfs":
        lambda: _simulate_fast("open", "fcfs", 4),
    "sweep_grid-jnp-lanes": lambda: _sweep_grid("jnp", "lanes"),
    "sweep_grid-fused-vmap": lambda: _sweep_grid("fused", "vmap"),
    "session_batch-jnp-lanes": lambda: _session_batch("jnp", "lanes"),
    "session_batch-fused-vmap": lambda: _session_batch("fused", "vmap"),
    "protocol-holds-closed": lambda: _protocol_holds("closed"),
    "protocol-holds-open": lambda: _protocol_holds("open"),
    "protocol-catches-S": lambda: _protocol_catches("tRRDS", {"tRRDS": 1}),
    "protocol-catches-L": lambda: _protocol_catches("tRRDL", {"tRRDL": 4}),
    "protocol-catches-PRE": lambda: _protocol_catches("tWTP", {"tWTP": 1}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_jedec(case):
    CASES[case]()


def test_jedec_with_table1_gaps_is_table1():
    """With S gaps equal to the L gaps and a gate that never holds (the
    defaults), the jedec circuit issues exactly what table1 does."""
    base = MemSimConfig(**dict(TOPOLOGY, timing_model="table1"))
    tr = trace(5)
    for backend in ("jnp", "fused"):
        a = simulate_fast(dataclasses.replace(base, fsm_backend=backend),
                          tr, HORIZON)
        b = simulate_fast(dataclasses.replace(base, fsm_backend=backend,
                                              timing_model="jedec"),
                          tr, HORIZON)
        for f in RECORDS:
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
        assert b.counters["grants_l_bound"] == 0


def test_jedec_validation():
    with pytest.raises(ValueError, match="tRRDS=8 .* must be <= tRRDL=6"):
        cfg(tRRDS=8).validate()
    with pytest.raises(ValueError, match="tCCDS=9"):
        simulate_fast(cfg(), trace(0), 100,
                      params=cfg(tCCDS=9, tCCDL=10).runtime()._replace(
                          tCCDL=6))
    with pytest.raises(ValueError, match="split \"pallas\""):
        cfg(fsm_backend="pallas").validate()
    with pytest.raises(ValueError, match="timing_model"):
        MemSimConfig(timing_model="ddr6")
    # table1 ignores the S gaps, whatever they are
    MemSimConfig(tRRDS=8, tRRDL=6).validate()
