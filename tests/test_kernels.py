"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.params import MemSimConfig
from repro.kernels.addr_map.ops import addr_map
from repro.kernels.bank_fsm.ops import bank_fsm_step
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.flash_attention.ops import attention
from repro.kernels.flash_attention.ref import gqa_attention_ref
from repro.models.blocked_attention import blocked_attention


# ------------------------------------------------------------- bank_fsm ----

@pytest.mark.parametrize("topology", [
    dict(),                                     # default 32 banks
    dict(ranks=1, bankgroups=2, banks_per_group=2),   # 4 banks (padding path)
    dict(channels=2, ranks=2, bankgroups=4, banks_per_group=4),  # 64 banks
    dict(page_policy="open"),                   # open-page variant
    # pairwise-DISTINCT timings: the defaults collide (tRP == tRCD* == tCL,
    # tCCDL == tRTW), so a swapped row in the kernel's packed RuntimeParams
    # vector would be invisible at defaults — this point pins every index
    dict(tRP=5, tRCDRD=7, tRCDWR=11, tCL=13, tXS=17, tRFC=50, tREFI=900,
         tCCDL=3, tWTR=9, tRTW=4, sref_idle_cycles=333, page_policy="open"),
    dict(tRP=6, tRCDRD=8, tRCDWR=12, tCL=15, tXS=19, tRFC=60, tREFI=800,
         sref_idle_cycles=123),                 # distinct timings, closed page
])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bank_fsm_kernel_matches_ref(topology, seed):
    cfg = MemSimConfig(**topology)
    rng = np.random.default_rng(seed)
    b = cfg.num_banks
    state = jnp.asarray(rng.integers(0, 14, size=(10, b)), jnp.int32)
    state = state.at[1].set(jnp.asarray(rng.integers(0, 30, (b,)), jnp.int32))
    state = state.at[3].set(jnp.asarray(rng.integers(0, 8000, (b,)), jnp.int32))
    state = state.at[8].set(jnp.asarray(rng.integers(-1, 50, (b,)), jnp.int32))
    state = state.at[9].set(jnp.asarray(rng.integers(0, 4, (b,)), jnp.int32))
    inputs = jnp.asarray(rng.integers(0, 2, size=(3, b)), jnp.int32)
    pop = jnp.asarray(rng.integers(0, 1000, size=(4, b)), jnp.int32)
    cycle = jnp.int32(int(rng.integers(0, 5000)))
    s_ref, f_ref = bank_fsm_step(cfg, state, inputs, pop, cycle, False)
    s_pal, f_pal = bank_fsm_step(cfg, state, inputs, pop, cycle, True, True)
    np.testing.assert_array_equal(np.asarray(s_ref), np.asarray(s_pal))
    np.testing.assert_array_equal(np.asarray(f_ref), np.asarray(f_pal))


@pytest.mark.parametrize("topology", [
    dict(),
    dict(ranks=1, bankgroups=2, banks_per_group=2),   # 4 banks (padding path)
    dict(tRFC=50, tREFI=900, sref_idle_cycles=333),
])
@pytest.mark.parametrize("seed", [0, 1])
def test_bank_event_bound_kernel_matches_ref(topology, seed):
    """The event-horizon engine's per-bank cycles-until-actionable: the
    Pallas kernel twin must agree bank-for-bank with the simulator's
    ``cycles_until_actionable`` on random packed states (WAIT timers,
    idle counters, refresh deadlines, SREF parking all drawn)."""
    from repro.core.bank_fsm import cycles_until_actionable
    from repro.kernels.bank_fsm.ops import bank_event_bound
    from repro.kernels.bank_fsm.ref import unpack_state

    cfg = MemSimConfig(**topology)
    rng = np.random.default_rng(seed)
    b = cfg.num_banks
    state = jnp.asarray(rng.integers(0, 14, size=(10, b)), jnp.int32)
    state = state.at[1].set(jnp.asarray(rng.integers(0, 40, (b,)), jnp.int32))
    state = state.at[2].set(jnp.asarray(rng.integers(0, 1200, (b,)), jnp.int32))
    state = state.at[3].set(jnp.asarray(rng.integers(0, 8000, (b,)), jnp.int32))
    cycle = jnp.int32(int(rng.integers(0, 5000)))
    rp = cfg.runtime()
    ref = bank_event_bound(state, cycle, rp, False)
    pal = bank_event_bound(state, cycle, rp, True, True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))
    direct = cycles_until_actionable(rp, unpack_state(state), cycle)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(direct))


@pytest.mark.parametrize("seed", [0, 1])
def test_bank_fsm_kernel_schedule_resolution(seed):
    """The packed-ABI ParamSchedule twin: with an [S, NP] parameter matrix
    + [S, 1] boundary vector the kernel must resolve the active segment
    in-kernel and agree with (a) the jnp oracle and (b) a constant-params
    call carrying the segment's point — at cycles on, just before and
    just after every boundary."""
    from repro.core import lane_schedule
    from repro.kernels.bank_fsm.ops import bank_event_bound

    cfg = MemSimConfig()
    spec = [(0, {}),
            (120, {"tCL": 20, "tRCDRD": 18, "tREFI": 1800}),
            (700, {"tCL": 28, "tRP": 17, "tRFC": 120, "tREFI": 900,
                   "sref_idle_cycles": 333, "page_policy": "open"})]
    sched = lane_schedule(cfg, spec)
    rng = np.random.default_rng(seed)
    b = cfg.num_banks
    state = jnp.asarray(rng.integers(0, 14, size=(10, b)), jnp.int32)
    state = state.at[1].set(jnp.asarray(rng.integers(0, 30, (b,)), jnp.int32))
    state = state.at[3].set(jnp.asarray(rng.integers(0, 8000, (b,)), jnp.int32))
    state = state.at[8].set(jnp.asarray(rng.integers(-1, 50, (b,)), jnp.int32))
    state = state.at[9].set(jnp.asarray(rng.integers(0, 4, (b,)), jnp.int32))
    inputs = jnp.asarray(rng.integers(0, 2, size=(3, b)), jnp.int32)
    pop = jnp.asarray(rng.integers(0, 1000, size=(4, b)), jnp.int32)
    import dataclasses
    seg_cfgs = [dataclasses.replace(cfg, **ov) for _, ov in spec]
    for cycle, seg in [(0, 0), (119, 0), (120, 1), (121, 1), (699, 1),
                       (700, 2), (701, 2), (5000, 2)]:
        cyc = jnp.int32(cycle)
        s_ref, f_ref = bank_fsm_step(cfg.topology(), state, inputs, pop,
                                     cyc, False, params=sched)
        s_pal, f_pal = bank_fsm_step(cfg.topology(), state, inputs, pop,
                                     cyc, True, True, params=sched)
        s_const, f_const = bank_fsm_step(cfg.topology(), state, inputs, pop,
                                         cyc, False,
                                         params=seg_cfgs[seg].runtime())
        np.testing.assert_array_equal(np.asarray(s_ref), np.asarray(s_pal),
                                      err_msg=f"cycle {cycle}")
        np.testing.assert_array_equal(np.asarray(f_ref), np.asarray(f_pal),
                                      err_msg=f"cycle {cycle}")
        np.testing.assert_array_equal(np.asarray(s_ref), np.asarray(s_const),
                                      err_msg=f"cycle {cycle} vs constant")
        b_ref = bank_event_bound(state, cyc, sched, False)
        b_pal = bank_event_bound(state, cyc, sched, True, True)
        b_const = bank_event_bound(state, cyc, seg_cfgs[seg].runtime(),
                                   False)
        np.testing.assert_array_equal(np.asarray(b_ref), np.asarray(b_pal),
                                      err_msg=f"bound cycle {cycle}")
        np.testing.assert_array_equal(np.asarray(b_ref), np.asarray(b_const),
                                      err_msg=f"bound cycle {cycle} const")


def test_bank_fsm_kernel_multi_cycle_rollout():
    """Kernel == ref over a 200-cycle closed-loop rollout."""
    cfg = MemSimConfig()
    rng = np.random.default_rng(3)
    b = cfg.num_banks
    state_r = state_p = (jnp.zeros((10, b), jnp.int32)
                         .at[3].set(cfg.tREFI).at[8].set(-1))
    for cycle in range(200):
        inputs = jnp.asarray(rng.integers(0, 2, size=(3, b)), jnp.int32)
        pop = jnp.asarray(rng.integers(0, 100, size=(4, b)), jnp.int32)
        state_r, f_r = bank_fsm_step(cfg, state_r, inputs, pop,
                                     jnp.int32(cycle), False)
        state_p, f_p = bank_fsm_step(cfg, state_p, inputs, pop,
                                     jnp.int32(cycle), True, True)
        assert (state_r == state_p).all() and (f_r == f_p).all(), cycle


def _seam_cfg(**kw):
    """Small topology for the fused-step audits (fast in interpret mode)."""
    return MemSimConfig(channels=2, ranks=1, bankgroups=2, banks_per_group=2,
                        queue_size=16, resp_queue_size=8, page_policy="open",
                        sched_policy="frfcfs", **kw)


def test_fused_step_schedule_boundary_seam():
    """Per-cycle audit of the fused single-dispatch step across
    ParamSchedule boundaries: stepping the SAME state through
    ``fused_cycle_step`` and the jnp ``cycle_step`` must agree on the full
    SimState pytree at every cycle — including the seam cycles (boundary,
    boundary-1, boundary+1) where the operating point flips and the
    in-kernel segment resolution must land on the right row."""
    import dataclasses

    from repro.core.engine import lane_schedule
    from repro.core.fused_step import fused_cycle_step
    from repro.core.simulator import cycle_step, init_state
    from repro.traces import BENCHMARKS

    cfg = _seam_cfg(fsm_backend="fused")
    sched = lane_schedule(cfg, [
        (0, {}), (120, {"tCL": 20, "tRCDRD": 18}),
        (700, {"tCL": 28, "tRP": 17})])
    topo = cfg.topology()
    topo_jnp = dataclasses.replace(topo, fsm_backend="jnp")
    trace = BENCHMARKS["trace_example"](n=60, gap=6)
    state = init_state(topo, sched, trace.num_requests)

    step_ref = jax.jit(lambda s, t: cycle_step(topo_jnp, sched, trace, s, t))
    # horizon = cycle + 1 clamps the returned delta to 0 (pure per-cycle)
    step_fus = jax.jit(
        lambda s, t: fused_cycle_step(topo, sched, trace, s, t, t + 1))
    for cycle in range(750):
        t = jnp.int32(cycle)
        ref = step_ref(state, t)
        fus, delta = step_fus(state, t)
        assert int(delta) == 0
        leaves_r = jax.tree_util.tree_leaves(ref)
        leaves_f = jax.tree_util.tree_leaves(fus)
        for lr, lf in zip(leaves_r, leaves_f):
            np.testing.assert_array_equal(
                np.asarray(lr), np.asarray(lf), err_msg=f"cycle {cycle}")
        state = ref


def test_fused_kernel_skip_rollout_matches_unfused():
    """Event-driven rollout: the fused kernel's (state, delta) per executed
    cycle must equal jnp ``cycle_step`` + ``engine._next_event`` (two
    dispatches + glue) followed by the shared ``_apply_skip``."""
    from repro.core import engine as eng
    from repro.core.engine import lane_schedule
    from repro.core.fused_step import fused_cycle_step
    from repro.core.simulator import cycle_step, init_state
    from repro.traces import BENCHMARKS

    cfg = _seam_cfg()
    sched = lane_schedule(cfg, [
        (0, {}), (150, {"tCL": 20, "tRCDRD": 18}), (400, {"tRP": 17})])
    topo = cfg.topology()
    trace = BENCHMARKS["trace_example"](n=40, gap=8)
    num_cycles = 4_000
    state = init_state(topo, sched, trace.num_requests)

    step_ref = jax.jit(lambda s, t: cycle_step(topo, sched, trace, s, t))
    next_ev = jax.jit(
        lambda s, nx: eng._next_event(topo, sched, trace, s, nx, num_cycles))
    step_fus = jax.jit(
        lambda s, t: fused_cycle_step(topo, sched, trace, s, t, num_cycles))
    skip = jax.jit(
        lambda s, d, nx: eng._apply_skip(topo, sched, s, d, nx))

    t, executed = 0, 0
    while t < num_cycles and executed < 120:
        tj = jnp.int32(t)
        ref = step_ref(state, tj)
        d_ref = int(next_ev(ref, tj + 1))
        fus, d_fus = step_fus(state, tj)
        assert d_ref == int(d_fus), f"delta diverged at cycle {t}"
        for lr, lf in zip(jax.tree_util.tree_leaves(ref),
                          jax.tree_util.tree_leaves(fus)):
            np.testing.assert_array_equal(
                np.asarray(lr), np.asarray(lf), err_msg=f"cycle {t}")
        state = skip(ref, jnp.int32(d_ref), tj + 1)
        t += 1 + d_ref
        executed += 1
    assert t > executed, "rollout never skipped — trace too dense to audit"


def test_fused_kernel_one_dispatch_per_cycle():
    """The acceptance metric: tracing one executed cycle of the fused path
    invokes the Pallas machinery exactly once, vs two for the split
    kernels (FSM step + event bound)."""
    from repro.core.engine import lane_schedule
    from repro.core.fused_step import fused_cycle_step
    from repro.core.simulator import init_state
    from repro.kernels.bank_fsm import bank_fsm as bf
    from repro.traces import BENCHMARKS

    cfg = _seam_cfg(fsm_backend="fused")
    sched = lane_schedule(cfg, None)
    topo = cfg.topology()
    trace = BENCHMARKS["trace_example"](n=20, gap=8)
    state = init_state(topo, sched, trace.num_requests)
    before = bf.trace_invocation_count()
    jax.make_jaxpr(
        lambda s: fused_cycle_step(topo, sched, trace, s, jnp.int32(3),
                                   jnp.int32(100)))(state)
    assert bf.trace_invocation_count() - before == 1


@pytest.mark.parametrize("platform,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None)])
def test_platform_alone_picks_interpret_mode(monkeypatch, platform,
                                             interpret):
    """The interpreter on CPU, the compiled kernel on TPU, and an error on
    any other platform: no probe, no override, no fallback."""
    from repro.kernels.bank_fsm import ops

    monkeypatch.setattr(ops.jax, "default_backend", lambda: platform)
    monkeypatch.setenv("MEMSIM_PALLAS_INTERPRET", "1")   # no longer read
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            ops.default_interpret()
    else:
        assert ops.default_interpret() is interpret


# ------------------------------------------------------------- addr_map ----

@pytest.mark.parametrize("n", [64, 1000, 4096])
@pytest.mark.parametrize("topology", [dict(), dict(channels=2)])
def test_addr_map_kernel_matches_ref(n, topology):
    cfg = MemSimConfig(**topology)
    rng = np.random.default_rng(n)
    addr = jnp.asarray(rng.integers(0, 1 << 28, size=(n,)), jnp.int32)
    ref = addr_map(cfg, addr, False)
    pal = addr_map(cfg, addr, True, True)
    for a, b in zip(ref, pal):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_addr_map_histogram_total():
    cfg = MemSimConfig()
    addr = jnp.arange(512, dtype=jnp.int32)
    _, _, _, hist = addr_map(cfg, addr, True, True)
    assert int(hist.sum()) == 512
    # sequential addresses interleave uniformly across banks
    assert int(hist.max()) == int(hist.min())


# ------------------------------------------------------ flash attention ----

@pytest.mark.parametrize("shape", [
    (1, 4, 128, 64, 4),    # MHA-ish
    (2, 8, 256, 64, 2),    # GQA group 4
    (1, 8, 256, 128, 1),   # MQA-to-1kv... hkv=8/8
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(shape, causal, dtype):
    b, hq, s, d, hkv = shape
    rng = np.random.default_rng(42)
    q = jnp.asarray(rng.standard_normal((b, hq, s, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), dtype)
    ref = attention(q, k, v, causal, False)
    pal = attention(q, k, v, causal, True, True)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_blocked_attention_matches_ref():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 8, 256, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 2, 256, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 2, 256, 32)), jnp.float32)
    for causal in (True, False):
        out = blocked_attention(q, k, v, causal=causal, block_q=64, block_k=128)
        ref = gqa_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-6, rtol=2e-6)


def test_blocked_attention_dv_neq_dk():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 4, 128, 48)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 4, 128, 48)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 4, 128, 32)), jnp.float32)
    out = blocked_attention(q, k, v, causal=True, block_q=64, block_k=64)
    assert out.shape == (1, 4, 128, 32)
    # spot-check against dense softmax
    s = (q[0, 0].astype(jnp.float32) @ k[0, 0].T) / np.sqrt(48)
    mask = np.tril(np.ones((128, 128), bool))
    s = np.where(mask, np.asarray(s), -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(out[0, 0]), p @ np.asarray(v[0, 0]),
                               atol=2e-5, rtol=2e-5)


# ----------------------------------------------------- decode attention ----

@pytest.mark.parametrize("shape", [
    (2, 8, 2, 512, 64),   # b, hq, hkv, s, d
    (1, 4, 4, 1024, 128),
    (4, 16, 2, 2048, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(shape, dtype):
    b, hq, hkv, s, d = shape
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((b, hq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), dtype)
    kv_len = jnp.asarray(rng.integers(1, s, size=(b,)), jnp.int32)
    ref = decode_attention(q, k, v, kv_len, False)
    pal = decode_attention(q, k, v, kv_len, True, True)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


# ------------------------------------------------------- selective scan ----

@pytest.mark.parametrize("shape", [
    (2, 64, 32, 8),      # B, T, D, S — unaligned small
    (1, 512, 512, 16),   # TPU-aligned chunking path
    (3, 128, 64, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_selective_scan_sweep(shape, dtype):
    from repro.kernels.selective_scan.ops import selective_scan

    b, t, d, s = shape
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((b, t, d)) * 0.5, dtype)
    dt = jnp.asarray(np.abs(rng.standard_normal((b, t, d))) * 0.1, dtype)
    bc = jnp.asarray(rng.standard_normal((b, t, s)), dtype)
    cc = jnp.asarray(rng.standard_normal((b, t, s)), dtype)
    a = jnp.asarray(-np.abs(rng.standard_normal((d, s))) - 0.1, jnp.float32)
    y_ref, h_ref = selective_scan(x, dt, bc, cc, a, False)
    y_pal, h_pal = selective_scan(x, dt, bc, cc, a, True, True)
    tol = 3e-6 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y_pal, np.float32),
                               np.asarray(y_ref, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(h_pal), np.asarray(h_ref),
                               atol=tol, rtol=tol)
