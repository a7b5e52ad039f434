"""Quickstart: MemorySim standalone (the paper's core artifact in 40 lines).

Runs the conv2d microbenchmark trace through the RTL-level simulator AND
the DRAMSim3-like ideal reference, printing the Table-2-style comparison,
the latency breakdown, and the power report.

  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import MemSimConfig, simulate, simulate_ideal, stats
from repro.core.power import PowerConfig, energy_report
from repro.traces import conv2d

def main() -> None:
    enable_compile_cache()
    # 1. configuration: paper Table 1 timing parameters, queueSize=128
    cfg = MemSimConfig(queue_size=128)
    print(f"topology: {cfg.channels}ch x {cfg.ranks}rk x {cfg.bankgroups}bg "
          f"x {cfg.banks_per_group}ba = {cfg.num_banks} banks; "
          f"queueSize={cfg.queue_size}")

    # 2. a memory trace (analytic stand-in for the paper's Valgrind capture)
    trace = conv2d()
    print(f"trace: {trace.num_requests} requests, "
          f"{float(np.asarray(trace.is_write).mean()):.0%} writes")

    # 3. cycle-accurate RTL-level simulation (100k cycles, paper setting)
    res = simulate(cfg, trace, num_cycles=100_000)
    s = stats.latency_summary(res)
    print(f"\nMemorySim: {s['completed']}/{s['total']} completed, "
          f"mean latency {s['mean']:.0f} cycles "
          f"(reads {s['read_mean']:.0f} / writes {s['write_mean']:.0f})")

    # 4. ideal open-page reference (what DRAMSim3 effectively runs)
    ideal = simulate_ideal(cfg, trace)
    d = stats.cycle_diffs(res, np.asarray(ideal.t_complete))
    print(f"vs ideal:  read diff {d.read_diff_avg:.0f}±{d.read_diff_std:.0f}, "
          f"write diff {d.write_diff_avg:.0f}±{d.write_diff_std:.0f} "
          f"(paper Table 2: ~102±59 / ~171±154)")

    # 5. where the cycles go (paper Fig 8)
    b = stats.latency_breakdown(res)
    print(f"breakdown: reqQueue {b['req_queue_pct']:.0f}% | "
          f"bank queue {b['bank_queue_pct']:.0f}% | "
          f"service {b['service_pct']:.0f}%")

    # 6. integrated power model (beyond-paper: no DRAMPower side-car needed)
    rep = energy_report(res.counters, PowerConfig())
    print(f"energy: {rep['total_energy_uj']:.1f} uJ total "
          f"({rep['command_energy_uj']:.1f} commands + "
          f"{rep['background_energy_uj']:.1f} background), "
          f"avg {rep['avg_power_mw_per_bank']:.1f} mW/bank")


if __name__ == "__main__":
    main()
