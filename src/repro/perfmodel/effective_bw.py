"""Memsim-refined memory roofline: effective (not peak) HBM bandwidth.

The paper's thesis applied to our own workloads: a behavioural roofline
assumes peak DRAM bandwidth, but bank conflicts, refresh, closed-page
overheads and queue backpressure make *effective* bandwidth
workload-dependent. This module converts an (arch x shape) cell's HBM
traffic into a DRAM access trace (repro.traces.llm_workload), runs both
the RTL-level simulator and the ideal model over it, and reports

    efficiency = ideal_cycles_at_peak / simulated_cycles

so the roofline memory term can be divided by that efficiency — the
beyond-paper integration recorded in EXPERIMENTS.md §Perf-beyond.

:func:`grid_study` closes the ROADMAP "LLM workload loop": the decode /
prefill / train streams of one architecture run against a whole runtime
parameter grid (timings x page policy x scheduler x refresh x queue depth)
as batch lanes of ONE compiled program (``repro.core.engine``), yielding
an effective-bandwidth-efficiency row per (stream, config) cell.
"""

from __future__ import annotations

import dataclasses
import itertools
import os
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from repro.core import MemSimConfig, simulate, simulate_batch, simulate_ideal
from repro.core.engine import _stream_threshold, grid_points, sweep_grid
from repro.traces import llm_workload


@dataclasses.dataclass
class EffectiveBW:
    name: str
    requests: int
    bytes_per_request: float
    sim_cycles: int
    ideal_cycles: int
    efficiency: float          # effective/peak bandwidth ratio
    read_latency_mean: float
    refresh_share: float


def _row_from_result(name: str, res, ideal_span: int, bpr: float,
                     horizon: int) -> EffectiveBW:
    done = res.completed
    sim_span = int(res.t_complete[done].max()) if done.any() else horizon
    lat = res.latency[done & (res.is_write == 0)]
    counts = res.counters["cmd_counts"]
    total_cmds = max(int(counts[1:6].sum()), 1)
    return EffectiveBW(
        name=name,
        requests=int(done.sum()),
        bytes_per_request=bpr,
        sim_cycles=sim_span,
        ideal_cycles=ideal_span,
        efficiency=min(1.0, ideal_span / max(sim_span, 1)),
        read_latency_mean=float(lat.mean()) if lat.size else float("nan"),
        refresh_share=float(counts[5]) / total_cmds,
    )


def measure(name: str, traffic: llm_workload.WorkloadTraffic,
            cfg: MemSimConfig = MemSimConfig(),
            target_requests: int = 8000, seed: int = 0) -> EffectiveBW:
    trace, bpr = llm_workload.synthesize(traffic, target_requests, seed=seed)
    horizon = int(np.asarray(trace.t).max()) + 200_000
    res = simulate(cfg, trace, num_cycles=horizon)
    ideal = simulate_ideal(cfg, trace)
    ideal_span = int(np.asarray(ideal.t_complete).max())
    return _row_from_result(name, res, ideal_span, bpr, horizon)


#: timing fields the ideal open-page reference consumes (it ignores
#: policies and queue depths) — the cache key subset for its spans.
_IDEAL_FIELDS = ("tRP", "tRCDRD", "tRCDWR", "tCCDL", "tCL", "tRFC", "tREFI")


def _stream_ckpt_dir(checkpoint_dir: Optional[str], si: int,
                     sname: str) -> Optional[str]:
    """Per-stream checkpoint subdirectory of a grid study (each stream is
    its own streaming sweep with its own manifest/chunks)."""
    if checkpoint_dir is None:
        return None
    return os.path.join(checkpoint_dir, f"stream_{si:02d}_{sname}")


def grid_study(streams: Sequence[Tuple[str, llm_workload.WorkloadTraffic]],
               grid: Mapping[str, Sequence],
               cfg: MemSimConfig = MemSimConfig(),
               target_requests: int = 4000, seed: int = 0,
               tail_cycles: int = 50_000,
               batch_mode: str = "auto",
               stream: Optional[bool] = None,
               chunk_lanes: Optional[int] = None,
               memory_budget_bytes: Optional[int] = None,
               checkpoint_dir: Optional[str] = None,
               resume: bool = True,
               timings: Optional[dict] = None) -> List[Dict]:
    """Effective bandwidth of every (stream x config) cell, one compile.

    ``streams`` are named traffic splits (decode / prefill / train — see
    :mod:`repro.traces.llm_workload`); ``grid`` is a :func:`sweep_grid`
    axis dict over runtime parameters. All ``len(streams) * len(points)``
    lanes run through ONE compiled batched program on the cycle-skipping
    engine (the drained tail collapses, so the shared horizon costs ~zero);
    the ideal reference reuses one compiled scan across all lanes since its
    timing values are traced too. Returns one dict per cell:
    ``{stream, config, efficiency, read_latency_mean, refresh_share, ...}``.

    Mega-grids stream: above :func:`~repro.core.engine._stream_threshold`
    total lanes — or whenever ``checkpoint_dir`` is given or
    ``stream=True`` — each traffic stream runs as its own streaming
    :func:`~repro.core.engine.sweep_grid` (chunked under
    ``memory_budget_bytes`` / ``chunk_lanes``, checkpointed per stream
    under ``checkpoint_dir/stream_<i>_<name>``, resumable after a kill),
    bit-exact per cell vs the one-batch path.
    """
    points = grid_points(grid)
    lane_cfgs = [dataclasses.replace(cfg, **ov)
                 for _ in streams for ov in points]
    traces, bprs = [], []
    for name, traffic in streams:
        tr, bpr = llm_workload.synthesize(traffic, target_requests, seed=seed)
        traces.append(tr)
        bprs.append(bpr)
    horizon = max(int(np.asarray(tr.t).max()) for tr in traces) + tail_cycles

    if stream is None:
        stream = (checkpoint_dir is not None
                  or len(lane_cfgs) >= _stream_threshold())
    if stream:
        results = []
        for si, (sname, _) in enumerate(streams):
            results.extend(sweep_grid(
                cfg, traces[si], grid, num_cycles=horizon, stream=True,
                chunk_lanes=chunk_lanes,
                memory_budget_bytes=memory_budget_bytes,
                checkpoint_dir=_stream_ckpt_dir(checkpoint_dir, si, sname),
                resume=resume, timings=timings))
    else:
        cap = max(c.queue_size for c in lane_cfgs)
        rcap = max(c.resp_queue_size for c in lane_cfgs)
        cfg_cap = dataclasses.replace(cfg, queue_size=cap,
                                      resp_queue_size=rcap)
        lane_traces = [traces[si] for si in range(len(streams))
                       for _ in points]
        results = simulate_batch(
            cfg_cap, lane_traces, num_cycles=horizon,
            queue_sizes=[c.queue_size for c in lane_cfgs],
            resp_queue_sizes=[c.resp_queue_size for c in lane_cfgs],
            params=[c.runtime() for c in lane_cfgs], lane_cfgs=lane_cfgs,
            batch_mode=batch_mode, timings=timings)

    # the ideal reference ignores policies and queue depths, so cache its
    # span per (stream, timing-relevant parameter subset) — a policy/depth
    # grid costs one ideal scan per stream, not one per cell
    ideal_spans: Dict[tuple, int] = {}

    def ideal_span_for(si: int, c: MemSimConfig) -> int:
        key = (si,) + tuple(getattr(c, f) for f in _IDEAL_FIELDS)
        if key not in ideal_spans:
            ideal = simulate_ideal(c, traces[si])
            ideal_spans[key] = int(np.asarray(ideal.t_complete).max())
        return ideal_spans[key]

    rows = []
    for (si, (sname, _)), (pi, ov) in itertools.product(
            enumerate(streams), enumerate(points)):
        li = si * len(points) + pi
        res = results[li]
        bw = _row_from_result(sname, res, ideal_span_for(si, lane_cfgs[li]),
                              bprs[si], horizon)
        rows.append({"stream": sname, "config": dict(ov),
                     **dataclasses.asdict(bw)})
    return rows


#: shape fields the ideal open-page reference is additionally sensitive to
#: on a topology grid (bank counts change its per-bank recurrence); joined
#: with ``_IDEAL_FIELDS`` to key its cached spans per stream.
_IDEAL_TOPO_FIELDS = ("channels", "ranks", "bankgroups", "banks_per_group",
                      "column_bits", "mem_words")


def topo_grid_study(streams: Sequence[Tuple[str, llm_workload.WorkloadTraffic]],
                    grid: Mapping[str, Sequence],
                    cfg: MemSimConfig = MemSimConfig(),
                    target_requests: int = 4000, seed: int = 0,
                    tail_cycles: int = 50_000,
                    stream: Optional[bool] = None,
                    chunk_lanes: Optional[int] = None,
                    memory_budget_bytes: Optional[int] = None,
                    checkpoint_dir: Optional[str] = None,
                    resume: bool = True,
                    timings: Optional[dict] = None) -> List[Dict]:
    """Effective bandwidth across *hardware shapes*: every (stream x
    topology x runtime) cell via :func:`repro.core.engine.sweep_topologies`
    — one overlapped compile per distinct :class:`Topology`, runtime axes
    batched as lanes within each.

    ``grid`` may mix structural axes (``channels``, ``banks_per_group``,
    ...) with runtime axes (timings, policies, queue depths). Returns one
    dict per cell: ``{stream, config, num_banks, efficiency,
    read_latency_mean, refresh_share, ...}`` — the design-space table the
    paper motivates (how much effective bandwidth does another channel or
    doubled banks actually buy this workload?).

    The streaming knobs (``stream`` / ``chunk_lanes`` /
    ``memory_budget_bytes`` / ``checkpoint_dir`` / ``resume``) pass
    straight through to :func:`~repro.core.engine.sweep_topologies`, with
    each stream checkpointing under its own
    ``checkpoint_dir/stream_<i>_<name>`` subdirectory.
    """
    from repro.core.engine import sweep_topologies

    rows = []
    ideal_spans: Dict[tuple, int] = {}
    for si, (sname, traffic) in enumerate(streams):
        tr, bpr = llm_workload.synthesize(traffic, target_requests,
                                          seed=seed)
        horizon = int(np.asarray(tr.t).max()) + tail_cycles
        sweep = sweep_topologies(cfg, tr, grid, num_cycles=horizon,
                                 stream=stream, chunk_lanes=chunk_lanes,
                                 memory_budget_bytes=memory_budget_bytes,
                                 checkpoint_dir=_stream_ckpt_dir(
                                     checkpoint_dir, si, sname),
                                 resume=resume, timings=timings)
        for point, res in zip(sweep.points, sweep.results):
            c = res.cfg
            key = ((sname,)
                   + tuple(getattr(c, f) for f in _IDEAL_FIELDS)
                   + tuple(getattr(c, f) for f in _IDEAL_TOPO_FIELDS))
            if key not in ideal_spans:
                ideal = simulate_ideal(c, tr)
                ideal_spans[key] = int(np.asarray(ideal.t_complete).max())
            bw = _row_from_result(sname, res, ideal_spans[key], bpr,
                                  horizon)
            rows.append({"stream": sname, "config": dict(point),
                         "num_banks": c.num_banks,
                         **dataclasses.asdict(bw)})
    return rows


def topo_llm_grid_study(arch_name: str, params_bytes_per_dev: float,
                        kv_bytes_per_dev: float, act_bytes_per_dev: float,
                        grid: Mapping[str, Sequence], **kw) -> List[Dict]:
    """The ISSUE-4 topology loop: decode + prefill streams of one
    architecture against a hardware-shape grid — effective bandwidth vs
    channels/banks for the two serving-critical streams."""
    streams = [
        ("decode", llm_workload.decode_step_traffic(
            arch_name, params_bytes_per_dev, kv_bytes_per_dev)),
        ("prefill", llm_workload.prefill_step_traffic(
            arch_name, params_bytes_per_dev, act_bytes_per_dev,
            kv_bytes_per_dev * 0.5)),
    ]
    return topo_grid_study(streams, grid, **kw)


def dvfs_study(streams: Sequence[Tuple[str, llm_workload.WorkloadTraffic]],
               schedules: Optional[Sequence[Tuple[str, object]]] = None,
               cfg: MemSimConfig = MemSimConfig(),
               target_requests: int = 4000, seed: int = 0,
               tail_cycles: int = 50_000,
               batch_mode: str = "auto",
               timings: Optional[dict] = None) -> List[Dict]:
    """Effective bandwidth under time-varying (DVFS / thermal-throttle)
    parameter schedules: every (stream x schedule) cell as lanes of ONE
    compiled batched program.

    ``schedules`` are named specs in any :func:`repro.core.engine.lane_schedule`
    form — typically the segment-spec lists of
    :func:`repro.traces.llm_workload.thermal_throttle_schedule`. When
    omitted, the canonical boost/sustained/throttled trajectory is built
    at a mild and an aggressive throttle **scaled to the actual simulated
    horizon** (so every operating point genuinely activates), plus the
    constant nominal point as the control row. Efficiency is reported
    against the *un-throttled* ideal reference (``cfg`` at its nominal
    operating point): "how much of the nominal-silicon ideal does this
    stream keep under this throttle trajectory". Each row additionally
    carries ``seg_cycle_frac`` — the exact fraction of the horizon spent
    under each operating point (the engine's per-segment cycle counters,
    exact under event-horizon skipping).
    """
    from repro.core import lane_schedule

    traces, bprs = [], []
    for name, traffic in streams:
        tr, bpr = llm_workload.synthesize(traffic, target_requests, seed=seed)
        traces.append(tr)
        bprs.append(bpr)
    horizon = max(int(np.asarray(tr.t).max()) for tr in traces) + tail_cycles
    if schedules is None:
        schedules = [
            ("nominal", None),
            ("throttle_mild", llm_workload.thermal_throttle_schedule(
                horizon, throttle_scale=1.5)),
            ("throttle_hard", llm_workload.thermal_throttle_schedule(
                horizon, throttle_scale=2.0, throttle_refresh_scale=4)),
        ]

    lane_traces = [traces[si] for si in range(len(streams))
                   for _ in schedules]
    lane_scheds = [lane_schedule(cfg, spec)
                   for _ in streams for _, spec in schedules]
    results = simulate_batch(
        cfg, lane_traces, num_cycles=horizon,
        params=lane_scheds, batch_mode=batch_mode, timings=timings)

    ideal_spans: Dict[tuple, int] = {}

    def ideal_span_for(si: int) -> int:
        if si not in ideal_spans:
            ideal = simulate_ideal(cfg, traces[si])
            ideal_spans[si] = int(np.asarray(ideal.t_complete).max())
        return ideal_spans[si]

    rows = []
    for (si, (sname, _)), (ci, (cname, _)) in itertools.product(
            enumerate(streams), enumerate(schedules)):
        li = si * len(schedules) + ci
        res = results[li]
        bw = _row_from_result(f"{sname}:{cname}", res, ideal_span_for(si),
                              bprs[si], horizon)
        seg = np.asarray(res.counters["seg_cycles"], dtype=np.int64)
        total = float(max(int(seg.sum()), 1))
        rows.append({"stream": sname, "schedule": cname,
                     "seg_cycle_frac": [round(int(c) / total, 4)
                                        for c in seg],
                     **dataclasses.asdict(bw)})
    return rows


def dvfs_llm_study(arch_name: str, params_bytes_per_dev: float,
                   kv_bytes_per_dev: float, act_bytes_per_dev: float,
                   schedules: Optional[Sequence[Tuple[str, object]]] = None,
                   **kw) -> List[Dict]:
    """The ISSUE-5 DVFS loop: decode + prefill streams of one architecture
    under thermal-throttle schedules — effective bandwidth per (stream,
    operating-point trajectory) for the two serving-critical streams.

    Default ``schedules`` (see :func:`dvfs_study`): the canonical
    boost/sustained/throttled trajectory
    (:func:`~repro.traces.llm_workload.thermal_throttle_schedule`) at a
    mild and an aggressive throttle scaled to the actual simulated
    horizon, plus the constant nominal point as the control row.
    """
    streams = [
        ("decode", llm_workload.decode_step_traffic(
            arch_name, params_bytes_per_dev, kv_bytes_per_dev)),
        ("prefill", llm_workload.prefill_step_traffic(
            arch_name, params_bytes_per_dev, act_bytes_per_dev,
            kv_bytes_per_dev * 0.5)),
    ]
    return dvfs_study(streams, schedules, **kw)


def cxl_tier_point(cfg: MemSimConfig, interleave_log2: int,
                   cxl_frac_log2: int, *, latency_adder: int = 30,
                   link_ccd_scale: int = 2, refi_scale: int = 1):
    """One tier-stacked parameter point for a tiered ``cfg``: tier 0 is the
    config's nominal DRAM timing, tier 1 the CXL expander — the nominal
    point plus a link-latency adder on the access path (tCL/tRCDRD/tRCDWR),
    a narrower link modeled as a stretched column-to-column gap
    (tCCDL/tWTR/tRTW x ``link_ccd_scale``), and optionally denser refresh
    (``tREFI / refi_scale``). Placement flags are tier-uniform traced data,
    so a (capacity split x interleave x timing) grid sweeps as lanes of one
    compiled program."""
    from repro.core.params import tiered_params

    dram = cfg.runtime()._replace(tier_interleave_log2=interleave_log2,
                                  tier_cxl_frac_log2=cxl_frac_log2)
    cxl = dram._replace(
        tCL=dram.tCL + latency_adder,
        tRCDRD=dram.tRCDRD + latency_adder,
        tRCDWR=dram.tRCDWR + latency_adder,
        tCCDL=dram.tCCDL * link_ccd_scale,
        tWTR=dram.tWTR * link_ccd_scale,
        tRTW=dram.tRTW * link_ccd_scale,
        tREFI=max(dram.tREFI // max(refi_scale, 1), dram.tRFC + 1),
    )
    return tiered_params(dram, cxl)


def cxl_tier_study(cfg: Optional[MemSimConfig] = None,
                   capacity_splits: Sequence[int] = (1, 2),
                   interleaves: Sequence[int] = (6, 8),
                   *, latency_adder: int = 30, link_ccd_scale: int = 2,
                   tokens: int = 32, chunks: int = 16,
                   tail_cycles: int = 30_000, seed: int = 0,
                   batch_mode: str = "vmap", bit_check: bool = True,
                   timings: Optional[dict] = None) -> List[Dict]:
    """Tiered-KV placement sweep: decode + prefill effective bandwidth vs
    DRAM:CXL capacity split and interleave ratio, every cell a lane of ONE
    compiled program on the tiered topology.

    ``capacity_splits`` are ``tier_cxl_frac_log2`` values (``k`` — the CXL
    expander owns 1 of every ``2^k`` interleave blocks, a DRAM:CXL split of
    ``(2^k - 1):1``); ``interleaves`` are ``tier_interleave_log2`` values
    (words per placement block). Each lane pairs a tier-stacked parameter
    point (:func:`cxl_tier_point`) with a hot/cold-placement trace
    regenerated for its flags
    (:func:`repro.traces.llm_workload.tiered_decode_trace` /
    :func:`~repro.traces.llm_workload.tiered_prefill_trace`). The whole
    grid shares one compiled program because the timing rows and placement
    flags are traced data (``timings["compiles"] == 1``).

    Efficiency is against the untiered nominal-DRAM ideal reference (what
    an all-DRAM device at the nominal point would do), so the column reads
    as "how much of all-DRAM ideal bandwidth does this placement keep".
    ``bit_check=True`` (the acceptance gate) re-runs every lane through
    the per-cycle reference :func:`repro.core.simulate` and reports
    field-for-field identity in the row's ``bit_identical``.
    """
    if cfg is None:
        cfg = MemSimConfig(channels=2, tiers=2, cxl_channels=1)
    if cfg.tiers != 2:
        raise ValueError("cxl_tier_study needs a tiered config (tiers=2)")
    points = [(k, il) for k in capacity_splits for il in interleaves]
    streams = [
        ("decode", lambda il, k: llm_workload.tiered_decode_trace(
            tokens=tokens, interleave_log2=il, cxl_frac_log2=k, seed=seed)),
        ("prefill", lambda il, k: llm_workload.tiered_prefill_trace(
            chunks=chunks, interleave_log2=il, cxl_frac_log2=k, seed=seed)),
    ]
    lane_traces, lane_params, lane_meta = [], [], []
    for sname, build in streams:
        for k, il in points:
            lane_traces.append(build(il, k))
            lane_params.append(cxl_tier_point(
                cfg, il, k, latency_adder=latency_adder,
                link_ccd_scale=link_ccd_scale))
            lane_meta.append((sname, k, il))
    horizon = (max(int(np.asarray(tr.t).max()) for tr in lane_traces)
               + tail_cycles)
    results = simulate_batch(cfg, lane_traces, num_cycles=horizon,
                             params=lane_params, batch_mode=batch_mode,
                             timings=timings)

    # untiered nominal ideal reference: all-DRAM device at the nominal
    # point over the same request stream
    ideal_cfg = dataclasses.replace(cfg, tiers=1, cxl_channels=0)
    rows = []
    for li, ((sname, k, il), res) in enumerate(zip(lane_meta, results)):
        ideal = simulate_ideal(ideal_cfg, lane_traces[li])
        ideal_span = int(np.asarray(ideal.t_complete).max())
        bw = _row_from_result(f"{sname}:split{(1 << k) - 1}:1:il{il}", res,
                              ideal_span, float(llm_workload.BURST_BYTES),
                              horizon)
        row = {"stream": sname, "cxl_frac_log2": k,
               "dram_cxl_split": f"{(1 << k) - 1}:1",
               "interleave_log2": il,
               **dataclasses.asdict(bw)}
        ta = np.asarray(res.counters["tier_active_cycles"], np.int64)
        row["tier_active_cycles"] = [int(v) for v in ta]
        if bit_check:
            ref = simulate(cfg, lane_traces[li], num_cycles=horizon,
                           params=lane_params[li])
            same = all(
                np.array_equal(np.asarray(getattr(ref, f)),
                               np.asarray(getattr(res, f)))
                for f in ("t_admit", "t_dispatch", "t_start", "t_complete",
                          "rdata"))
            same = same and all(
                np.array_equal(np.asarray(ref.counters[c]),
                               np.asarray(res.counters[c]))
                for c in ref.counters)
            row["bit_identical"] = bool(same)
        rows.append(row)
    return rows


def saturation_knee(loads: Sequence[float],
                    tput: Sequence[float], *,
                    efficiency: float = 0.7) -> Optional[float]:
    """The saturation knee of a tokens/sec-vs-offered-load curve: the first
    load whose throughput gain falls below ``efficiency`` of the offered
    gain (doubling the load no longer comes close to doubling the output —
    the serving system has gone memory-bound). ``None`` when the curve
    still scales at its last point, and ``None`` on curve segments that
    carry no evidence — non-finite throughput, or an all-idle lane whose
    curve sits at zero (a 0 -> 0 step is not a knee, it is the NaN-with-
    flag convention's "nothing completed" case)."""
    for i in range(1, len(loads)):
        prev, cur = float(tput[i - 1]), float(tput[i])
        if not (np.isfinite(prev) and np.isfinite(cur)) or prev <= 0:
            continue
        load_gain = loads[i] / max(loads[i - 1], 1e-9)
        tput_gain = cur / prev
        if tput_gain < efficiency * load_gain:
            return float(loads[i])
    return None


def serving_row(tname: str, mix: str, load: float, res) -> Dict:
    """One serving-study row off a :class:`repro.serving.ServingResult`.
    Empty completion sets (an all-blocked lane: zero windows planned or
    zero requests ever finished) flag NaN per the ``_mean_std``
    convention instead of raising on ``mean``/``min`` of nothing."""
    from repro.core import stats

    ab = np.asarray(res.admitted_batch, np.float64)
    bt = np.asarray(res.batch_target, np.float64)
    return {
        "topology": tname, "mixture": mix,
        "offered_load_per_kcycle": float(load),
        "offered": res.offered, "completed": res.completed,
        "tokens": res.tokens, "cycles": res.cycles,
        "tokens_per_kcycle": res.tokens_per_kcycle,
        "admitted_batch_mean": (float(ab.mean()) if ab.size
                                else float("nan")),
        "admitted_batch_min": (int(ab.min()) if ab.size else 0),
        "batch_target_mean": (float(bt.mean()) if bt.size
                              else float("nan")),
        "queueing": stats.latency_percentiles(res.queueing),
        "service": stats.latency_percentiles(res.service),
    }


def serving_study(loads: Sequence[float] = (0.5, 1.0, 2.0, 4.0),
                  mixtures: Sequence[str] = ("chat",),
                  topologies=None, *, process: str = "poisson",
                  horizon: int = 10_000, window_cycles: int = 400,
                  serving=None, seed: int = 0, batch_lanes: bool = True,
                  timings: Optional[dict] = None) -> List[Dict]:
    """Closed-loop serving sweep: offered load x length mixture x topology.

    Unlike every open-loop study above, the address stream here is not
    fixed up front — the continuous-batching scheduler emits each window's
    traffic from what the memory system completed in the previous window,
    so tokens/sec saturates (the knee :func:`saturation_knee` finds) and
    the admitted batch shrinks under memory backpressure instead of the
    trace blindly queueing deeper.

    With ``batch_lanes`` (the default) each topology submits its whole
    load x mixture grid as lanes of ONE
    :func:`repro.serving.run_serving_batched` program — scenario count
    stops being a wall-clock multiplier — and the rows are bit-identical
    to the sequential (``batch_lanes=False``) path, which remains for
    runs whose sessions cannot share a compiled shape (heterogeneous
    per-scenario capacities) or for debugging one scenario at a time.

    ``topologies`` is ``[(name, cfg, params-or-None), ...]``; the default
    pairs a plain 2-channel DRAM device against a CXL-heavy tiered device
    (tier-stacked params from :func:`cxl_tier_point` with a deep link
    penalty) so the backpressure contrast is visible. Every run of one
    topology shares ONE compiled windowed program: the session capacity is
    fixed study-wide (max over scenarios, rounded up to a power of two),
    so ``timings["compiles"]`` lands at ``len(topologies)``.

    Rows carry tokens/kilocycle, admitted-batch statistics (mean/min and
    the AIMD target trajectory mean), and request-level p50/p95/p99
    queueing + service percentiles (:func:`repro.core.stats.latency_percentiles`).
    """
    from repro.serving import (ServingConfig, generate_request_batch,
                               run_serving, run_serving_batched,
                               session_capacity)

    serving = serving or ServingConfig()
    if topologies is None:
        cxl_cfg = MemSimConfig(channels=2, tiers=2, cxl_channels=1)
        topologies = [
            ("dram", MemSimConfig(channels=2), None),
            ("cxl", cxl_cfg,
             cxl_tier_point(cxl_cfg, cxl_cfg.tier_interleave_log2,
                            cxl_cfg.tier_cxl_frac_log2, latency_adder=200,
                            link_ccd_scale=8)),
        ]

    # every lane reuses the study seed verbatim (not spawn_seeds children):
    # a batched and a sequential run of the same study must feed identical
    # scenarios for the bit-identity contract to be checkable
    keys = [(mix, load) for mix in mixtures for load in loads]
    scenarios = dict(zip(keys, generate_request_batch(
        [dict(process=process, mixture=mix, rate_per_kcycle=load,
              horizon=horizon) for mix, load in keys],
        seed=seed, independent_streams=False)))

    # fixed study-wide capacity -> one compiled program per topology
    capacity = session_capacity(scenarios.values(), serving)

    rows = []
    for tname, cfg, params in topologies:
        if batch_lanes:
            res_by_key = dict(zip(keys, run_serving_batched(
                cfg, [scenarios[k] for k in keys], serving, params=params,
                window_cycles=window_cycles, capacity=capacity,
                timings=timings, seed=seed)))
        else:
            res_by_key = {k: run_serving(
                cfg, scenarios[k], serving, params=params,
                window_cycles=window_cycles, capacity=capacity,
                timings=timings, seed=seed) for k in keys}
        for mix in mixtures:
            curve = [serving_row(tname, mix, load, res_by_key[(mix, load)])
                     for load in loads]
            knee = saturation_knee([r["offered_load_per_kcycle"]
                                    for r in curve],
                                   [r["tokens_per_kcycle"] for r in curve])
            for r in curve:
                r["knee_load"] = knee
            rows.extend(curve)
    return rows


def llm_grid_study(arch_name: str, params_bytes_per_dev: float,
                   kv_bytes_per_dev: float, act_bytes_per_dev: float,
                   grid: Mapping[str, Sequence], **kw) -> List[Dict]:
    """The ROADMAP LLM-workload loop: decode + prefill + train streams of
    one architecture through a runtime-parameter grid sweep."""
    streams = [
        ("decode", llm_workload.decode_step_traffic(
            arch_name, params_bytes_per_dev, kv_bytes_per_dev)),
        ("prefill", llm_workload.prefill_step_traffic(
            arch_name, params_bytes_per_dev, act_bytes_per_dev,
            kv_bytes_per_dev * 0.5)),
        ("train", llm_workload.train_step_traffic(
            arch_name, params_bytes_per_dev, act_bytes_per_dev)),
    ]
    return grid_study(streams, grid, **kw)


def decode_efficiency(arch_name: str, params_bytes_per_dev: float,
                      kv_bytes_per_dev: float, **kw) -> EffectiveBW:
    tr = llm_workload.decode_step_traffic(arch_name, params_bytes_per_dev,
                                          kv_bytes_per_dev)
    return measure(arch_name + ":decode", tr, **kw)


def train_efficiency(arch_name: str, params_bytes_per_dev: float,
                     act_bytes_per_dev: float, **kw) -> EffectiveBW:
    tr = llm_workload.train_step_traffic(arch_name, params_bytes_per_dev,
                                         act_bytes_per_dev)
    return measure(arch_name + ":train", tr, **kw)
