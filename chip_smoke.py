"""Run the simulator's main path on one TPU chip and check it bit for bit.

    python chip_smoke.py                  # one chip: phases a, b, c
    python chip_smoke.py --chips 4        # four chips: the sharded sweep
                                          # against the same work on one
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # CPU rehearsal

Configuration: ``MemSimConfig(channels=2, queue_size=128,
fsm_backend="fused")`` (64 banks, the 64-entry response queue, the 64 Ki-word
backing store) driven by ``traces.llm_workload.decode_serving_trace()``:
1,632 requests over about 384k cycles, horizon 400,000.

  a. ``simulate_fast`` (fused kernel, event-horizon engine) against the
     per-cycle ``simulate`` on the ``jnp`` backend, field for field.
  b. ``sweep_grid`` over 64 lanes (tCL x tREFI x page x sched x queue depth)
     in the platform's default batch mode; four corner lanes against
     single-lane ``jnp`` ``simulate_fast`` runs.
  c. ``run_serving_batched``: 8 closed-loop serving lanes (load x mixture,
     Poisson arrivals over 10,000 cycles) on the 2-tier DRAM + CXL
     topology until they drain; lane 0 against a standalone
     ``run_serving`` on the same requests.

With ``--chips 4`` only the sweep path that spans devices runs, at horizon
100,000: phase b's grid sharded over the devices in ``vmap`` mode, plus a
4-topology ``sweep_topologies`` round-robined over them, each compared
lane for lane with the same work on one device.

Per phase the script prints compile seconds, executed steps, simulated
cycles per wall second and ``bit_identical``. These are information, not
metrics. The last line is one JSON object naming the device. The exit code
is 0 only on a TPU with every phase bit-identical; 1 if a phase failed, 2
if the repo's sources are missing, 3 if the platform is not a TPU (also
after a passing ``--tiny`` rehearsal off the chip).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

GRID = {"tCL": [14, 18], "tREFI": [3600, 7200],
        "page_policy": ["closed", "open"], "sched_policy": ["fcfs", "frfcfs"],
        "queue_size": [16, 32, 64, 128]}
TINY_GRID = {"tCL": [14, 18], "tREFI": [3600], "page_policy": ["closed", "open"],
             "sched_policy": ["frfcfs"], "queue_size": [16, 128]}
TOPO_GRID = {"channels": [1, 2], "banks_per_group": [2, 4],
             "tREFI": [3600, 7200], "queue_size": [16, 128]}
LOADS = (0.5, 1.0, 2.0, 4.0)
MIXTURES = ("chat", "summarize")


def corners(grid):
    """Four corner points of a grid: every axis at its first value, every
    axis at its last, and the two alternations."""
    axes = list(grid)
    picks = [[0] * len(axes), [-1] * len(axes),
             [0 if i % 2 == 0 else -1 for i in range(len(axes))],
             [-1 if i % 2 == 0 else 0 for i in range(len(axes))]]
    return [{a: grid[a][p] for a, p in zip(axes, pick)} for pick in picks]


def report(name, timings, sim_cycles, bit_identical, wall_s):
    rate = sim_cycles / wall_s if wall_s > 0 else float("nan")
    print(f"phase {name}: compile_s={timings.get('compile_s', 0.0)!r} "
          f"steps={timings.get('steps')} "
          f"sim_cycles_per_wall_s={rate!r} bit_identical={bit_identical}",
          flush=True)


def phase_a(env):
    """simulate_fast on the fused kernel vs the per-cycle jnp reference."""
    import dataclasses

    from repro.core import simulate, simulate_fast

    cfg, trace, horizon = env["cfg"], env["trace"], env["horizon"]
    tm = {}
    fast = simulate_fast(cfg, trace, horizon, timings=tm)
    ref = simulate(dataclasses.replace(cfg, fsm_backend="jnp"), trace,
                   horizon)
    bad = env["mismatches"](ref, fast, "a")
    report("a", tm, horizon, not bad, tm["run_s"])
    return bad


def phase_b(env):
    """64-lane sweep_grid in the platform's batch mode; corners vs jnp."""
    import dataclasses

    from repro.core import simulate_fast, sweep_grid

    cfg, trace, horizon, grid = (env["cfg"], env["trace"], env["horizon"],
                                 env["grid"])
    tm = {}
    res = sweep_grid(cfg, trace, grid, horizon, timings=tm)
    keys = list(grid)
    bad = []
    for pt in corners(grid):
        lane = next(r for r in res
                    if all(getattr(r.cfg, k) == v for k, v in pt.items()))
        q = pt.get("queue_size", cfg.queue_size)
        lane_cfg = dataclasses.replace(
            cfg, fsm_backend="jnp",
            **{k: v for k, v in pt.items() if k != "queue_size"})
        ref = simulate_fast(lane_cfg, trace, horizon, queue_size=q)
        bad += env["mismatches"](ref, lane, "b" + str([pt[k] for k in keys]))
    report("b", tm, horizon * len(res), not bad, tm["run_s"])
    return bad


def phase_c(env):
    """8 closed-loop serving lanes on the tiered topology vs run_serving."""
    import numpy as np

    from repro.core import MemSimConfig
    from repro.serving import (ServingConfig, generate_request_batch,
                               run_serving, run_serving_batched,
                               session_capacity)

    # the tiered topology at its own default DRAM + CXL parameters
    cfg = MemSimConfig(channels=2, tiers=2, cxl_channels=1,
                       fsm_backend="fused")
    params = None
    serving = ServingConfig()
    lists = generate_request_batch(
        [dict(process="poisson", mixture=m, rate_per_kcycle=r,
              horizon=env["serve_horizon"])
         for m in MIXTURES for r in LOADS], seed=0)
    capacity = session_capacity(lists, serving)
    window = env["serve_window"]
    tm = {}
    t0 = time.perf_counter()
    batched = run_serving_batched(cfg, lists, serving, params=params,
                                  window_cycles=window, capacity=capacity,
                                  timings=tm)
    wall = time.perf_counter() - t0
    alone = run_serving(cfg, lists[0], serving, params=params,
                        window_cycles=window, capacity=capacity)
    a, b = alone, batched[0]
    bad = []
    for f in ("offered", "completed", "tokens", "cycles", "admitted_batch",
              "batch_target"):
        if getattr(a, f) != getattr(b, f):
            bad.append(f"c:{f}")
    for f in ("queueing", "service"):
        if not np.array_equal(getattr(a, f), getattr(b, f)):
            bad.append(f"c:{f}")
    # the request records (the repo's serving contract): a lane that drains
    # early rides inert to the batch's end, so its time-integrated counters
    # keep counting past its own exit cycle
    ra, rb = a.session.result(), b.session.result()
    for f in ("t_admit", "t_dispatch", "t_start", "t_complete", "rdata"):
        if not np.array_equal(getattr(ra, f), getattr(rb, f)):
            bad.append(f"c:{f}")
    if any(r.completed != r.offered for r in batched):
        bad.append("c:lanes did not drain")
    lane_cycles = sum(r.cycles for r in batched)
    report("c", tm, lane_cycles, not bad, wall)
    return bad


def phase_mesh(env):
    """The sweep spread over every device vs the same work on one."""
    import dataclasses

    import jax

    from repro.core import sweep_grid, sweep_topologies

    cfg, trace, horizon, grid = (env["cfg"], env["trace"], env["horizon"],
                                 env["grid"])
    n_dev = len(jax.devices())
    bad = []

    tm, tm1 = {}, {}
    t0 = time.perf_counter()
    spread = sweep_grid(cfg, trace, grid, horizon, batch_mode="vmap",
                        timings=tm)
    wall = time.perf_counter() - t0
    one = sweep_grid(cfg, trace, grid, horizon, batch_mode="vmap",
                     shard=False, timings=tm1)
    print(f"mesh grid: sharded={tm.get('sharded')} "
          f"devices_used={tm.get('devices_used')} "
          f"one_device={tm1.get('devices_used')}", flush=True)
    if not tm.get("sharded") or len(tm.get("devices_used", [])) != n_dev:
        bad.append("mesh:grid lanes not spread over every device")
    if len(tm1.get("devices_used", [])) != 1:
        bad.append("mesh:the comparison did not run on one device")
    for i, (r1, rn) in enumerate(zip(one, spread)):
        bad += env["mismatches"](r1, rn, f"mesh grid lane {i}")
    report("mesh-grid", tm, horizon * len(spread), not bad, wall)

    tm = {}
    t0 = time.perf_counter()
    topo = sweep_topologies(cfg, trace, TOPO_GRID, horizon, timings=tm)
    wall = time.perf_counter() - t0
    used = sorted({p["device"] for p in tm["per_topology"]})
    print(f"mesh topologies: {tm['topologies']} devices_used={used}",
          flush=True)
    if len(used) != min(n_dev, tm["topologies"]):
        bad.append("mesh:topologies not round-robined over the devices")
    topo_bad = []
    for t in topo.topologies:
        idx = [i for i, ti in enumerate(topo.topo_of_point) if ti
               == topo.topologies.index(t)]
        pts = [topo.points[i] for i in idx]
        structural = {k: pts[0][k] for k in ("channels", "banks_per_group")}
        rest = {k: v for k, v in TOPO_GRID.items() if k not in structural}
        ref = sweep_grid(dataclasses.replace(cfg, **structural), trace, rest,
                         horizon, batch_mode="vmap", shard=False,
                         capacity=max(TOPO_GRID["queue_size"]))
        for k, i in enumerate(idx):
            topo_bad += env["mismatches"](ref[k], topo.results[i],
                                          f"mesh topo {structural} lane {k}")
    report("mesh-topologies", tm, horizon * len(topo.results), not topo_bad,
           wall)
    return bad + topo_bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the path that spans four chips")
    ap.add_argument("--tiny", action="store_true",
                    help="tiny sizes for a CPU rehearsal (never a chip "
                         "result)")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: the repo's sources (src/repro) are not next to "
              "this script", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    cache_events = {"requests": 0, "hits": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            cache_events["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1

    jax.monitoring.register_event_listener(on_event)

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.tiny:
        print(f"chip_smoke: no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 3
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    import jax.numpy as jnp

    from benchmarks.run import _bit_mismatches
    from repro.core import MemSimConfig, engine
    from repro.traces.llm_workload import decode_serving_trace

    cfg = MemSimConfig(channels=2, queue_size=128, fsm_backend="fused")
    if args.tiny:
        env = dict(trace=decode_serving_trace(tokens=3, compute_gap=400),
                   horizon=2_000, grid=TINY_GRID, serve_horizon=2_000,
                   serve_window=400)
    else:
        # serving arrivals over 10,000 cycles (the serving study's horizon):
        # every lane drains by about 260,000 cycles
        env = dict(trace=decode_serving_trace(), horizon=400_000, grid=GRID,
                   serve_horizon=10_000, serve_window=2_000)
    if args.chips == 4:
        # the four-chip path: a quarter of the decode trace, still 10^5
        # cycles, so the one-device comparison stays short
        env["horizon"] = min(env["horizon"], 100_000)
    env.update(cfg=cfg, mismatches=_bit_mismatches)
    print(f"config: channels={cfg.channels} banks={cfg.num_banks} "
          f"queue_size={cfg.queue_size} "
          f"resp_queue_size={cfg.resp_queue_size} "
          f"mem_words={cfg.mem_words} requests={env['trace'].num_requests} "
          f"horizon={env['horizon']} compile_cache={cache_dir}", flush=True)

    # is the fused kernel a Mosaic kernel in the step the engine runs?
    lowered = engine._run_skip_jit.lower(
        cfg.topology(), env["trace"], jnp.int32(env["horizon"]),
        engine._sched_i32(cfg.runtime()), jnp.int32(cfg.queue_size),
        jnp.int32(cfg.resp_queue_size)).as_text()
    custom = "tpu_custom_call" in lowered
    print(f"fused step: tpu_custom_call={custom}", flush=True)
    failed = on_tpu and not custom

    phases = ([("mesh", phase_mesh)] if args.chips == 4 else
              [("a", phase_a), ("b", phase_b), ("c", phase_c)])
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            bad = fn(env)
        except Exception:  # a phase error fails the run, after the others
            traceback.print_exc()
            print(f"phase {name}: error", flush=True)
            failed = True
            continue
        # the whole phase, its references included
        print(f"phase {name}: phase_wall_s={time.perf_counter() - t0!r}",
              flush=True)
        if bad:
            print(f"phase {name}: mismatches {bad[:20]}", flush=True)
            failed = True

    print(f"compile cache: requests={cache_events['requests']} "
          f"hits={cache_events['hits']}", flush=True)
    if failed:
        return 1
    if not on_tpu:
        print(f"chip_smoke: rehearsal passed on {dev.platform}; not a chip "
              f"result", file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
