"""DRAMPower-style energy accounting (beyond-paper feature).

The paper calls out the loose "power-performance coupling" of standalone
estimators (DRAMPower, VAMPIRE) fed by cycle-stack traces as a limitation;
because MemorySim *is* the timing model, we integrate energy counters
directly into the cycle loop: per-command energies plus state-dependent
background power, in the style of the DRAMPower/Micron power model.

Constants are DDR4-2400-class (nJ per command / mW background), configurable.
Counters live in the scan carry as int64 command counts + per-state cycle
counts; Joules are derived post-simulation in :func:`energy_report`.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax.numpy as jnp
from jax import Array

from repro.core.params import NUM_CMDS


@dataclasses.dataclass(frozen=True)
class PowerConfig:
    # per-command energy, nanojoules (DDR4-class defaults)
    e_act_nj: float = 1.7
    e_pre_nj: float = 1.2
    e_rd_nj: float = 4.2
    e_wr_nj: float = 4.6
    e_ref_nj: float = 26.0
    # background power, milliwatts per bank-cycle bucket
    p_act_standby_mw: float = 45.0
    p_pre_standby_mw: float = 35.0
    p_sref_mw: float = 4.0
    clock_ghz: float = 1.2


#: the ``"jedec"`` timing model's grant counters: ACT/RD/WR grants whose
#: legal cycle a same-bank-group (L) window set, ACT/RD/WR grants a window
#: held after their bid began, and PRE grants the precharge gate held
JEDEC_COUNTERS = ("grants_l_bound", "grants_windowed", "pre_held")


def make_counters(num_banks: int, num_segments: int = 1,
                  num_tiers: int = 1, jedec: bool = False) -> Dict[str, Array]:
    jedec_counters = {k: jnp.zeros((), jnp.int32) for k in JEDEC_COUNTERS
                      } if jedec else {}
    return {
        **jedec_counters,
        "cmd_counts": jnp.zeros((NUM_CMDS,), jnp.int32),
        "sref_cycles": jnp.zeros((), jnp.int32),
        "active_cycles": jnp.zeros((), jnp.int32),   # banks not IDLE/SREF
        "idle_cycles": jnp.zeros((), jnp.int32),
        # cycles spent under each ParamSchedule segment (operating point):
        # the DVFS study's time-at-operating-point attribution. A constant
        # run is the degenerate one-segment schedule.
        "seg_cycles": jnp.zeros((num_segments,), jnp.int32),
        # per-memory-tier split of the same bank-cycle buckets (DRAM vs
        # CXL residency attribution). A single-tier run carries the
        # degenerate T=1 rows — identical totals to the scalar buckets.
        "tier_active_cycles": jnp.zeros((num_tiers,), jnp.int32),
        "tier_idle_cycles": jnp.zeros((num_tiers,), jnp.int32),
        "tier_sref_cycles": jnp.zeros((num_tiers,), jnp.int32),
    }


def _tier_state_counts(counters: Dict[str, Array], st: Array,
                       tier_idx) -> tuple:
    """Per-tier (sref, idle, active) bank counts for the current states.
    ``tier_idx`` is the static int32[B] bank->tier map (None for T=1)."""
    from repro.core.params import S_IDLE, S_SREF

    t = counters["tier_sref_cycles"].shape[0]
    sref_m = (st == S_SREF).astype(jnp.int32)
    idle_m = (st == S_IDLE).astype(jnp.int32)
    if t == 1 or tier_idx is None:
        sref = sref_m.sum().reshape(1)
        idle = idle_m.sum().reshape(1)
        per_tier_banks = jnp.full((1,), st.shape[0], jnp.int32)
    else:
        idx = jnp.asarray(tier_idx)
        zeros = jnp.zeros((t,), jnp.int32)
        sref = zeros.at[idx].add(sref_m)
        idle = zeros.at[idx].add(idle_m)
        per_tier_banks = zeros.at[idx].add(1)
    return sref, idle, per_tier_banks - sref - idle


def update_counters(
    counters: Dict[str, Array],
    issued_cmd: Array,     # int32[C]: command granted per channel (CMD_NOP if none)
    st: Array,             # int32[B] bank states
    seg: Array = 0,        # scalar int32: active ParamSchedule segment
    tier_idx=None,         # static int32[B] bank->tier map (None: one tier)
    grant_stats=None,      # "jedec": this edge's JEDEC_COUNTERS increments
) -> Dict[str, Array]:
    from repro.core.params import S_IDLE, S_SREF

    jedec = ({k: counters[k] + v for k, v in zip(JEDEC_COUNTERS, grant_stats)}
             if grant_stats is not None else {})
    one_hot = jnp.zeros((NUM_CMDS,), jnp.int32).at[issued_cmd].add(1)
    # CMD_NOP slot accumulates junk; zero it out at report time.
    sref = (st == S_SREF).sum().astype(jnp.int32)
    idle = (st == S_IDLE).sum().astype(jnp.int32)
    b = st.shape[0]
    t_sref, t_idle, t_active = _tier_state_counts(counters, st, tier_idx)
    return {
        **jedec,
        "cmd_counts": counters["cmd_counts"] + one_hot,
        "sref_cycles": counters["sref_cycles"] + sref,
        "idle_cycles": counters["idle_cycles"] + idle,
        "active_cycles": counters["active_cycles"] + (b - sref - idle),
        "seg_cycles": counters["seg_cycles"].at[seg].add(1),
        "tier_sref_cycles": counters["tier_sref_cycles"] + t_sref,
        "tier_idle_cycles": counters["tier_idle_cycles"] + t_idle,
        "tier_active_cycles": counters["tier_active_cycles"] + t_active,
    }


def skip_counters(
    counters: Dict[str, Array],
    st: Array,             # int32[B] bank states (frozen over the skip)
    delta: Array,          # scalar int32 number of inert cycles skipped
    channels: int,
    seg: Array = 0,        # scalar int32: segment every skipped cycle is in
    tier_idx=None,         # static int32[B] bank->tier map (None: one tier)
) -> Dict[str, Array]:
    """Delta-aware twin of :func:`update_counters`: exactly ``delta``
    applications of the per-cycle update under an all-NOP issue slate and
    frozen bank states — what every inert cycle contributes.

    Used by the event-horizon engine's ``_apply_skip``; keeping it next to
    :func:`update_counters` pins the SREF / idle / active-standby
    attribution (and the per-channel NOP accounting) to one place, so the
    energy_report of a skipped run is field-for-field identical to the
    per-cycle engine's. A ``delta`` of 0 is the identity.

    Segment attribution under time-varying params: the engine caps every
    skip at the next ``ParamSchedule`` boundary (``_next_event`` mins it
    in), so a skipped delta NEVER spans two segments — that cap is the
    split mechanism, and attributing the whole delta to ``seg`` (the
    segment of the first skipped cycle) keeps the per-operating-point
    cycle attribution exact against the per-cycle reference.
    """
    from repro.core.params import CMD_NOP, S_IDLE, S_SREF

    sref = (st == S_SREF).sum().astype(jnp.int32)
    idle = (st == S_IDLE).sum().astype(jnp.int32)
    b = st.shape[0]
    delta = jnp.asarray(delta, jnp.int32)
    t_sref, t_idle, t_active = _tier_state_counts(counters, st, tier_idx)
    return {
        **counters,  # the grant counters: no grant in an inert cycle
        # each skipped cycle issues CMD_NOP on every channel (junk slot,
        # but bit-identical to the per-cycle engine's one_hot accumulation)
        "cmd_counts": counters["cmd_counts"].at[CMD_NOP].add(delta * channels),
        "sref_cycles": counters["sref_cycles"] + delta * sref,
        "idle_cycles": counters["idle_cycles"] + delta * idle,
        "active_cycles": counters["active_cycles"] + delta * (b - sref - idle),
        "seg_cycles": counters["seg_cycles"].at[seg].add(delta),
        "tier_sref_cycles": counters["tier_sref_cycles"] + delta * t_sref,
        "tier_idle_cycles": counters["tier_idle_cycles"] + delta * t_idle,
        "tier_active_cycles": counters["tier_active_cycles"]
        + delta * t_active,
    }


def energy_report(counters: Dict[str, Array], pcfg: PowerConfig) -> Dict[str, float]:
    """Derive energy (µJ) and average power (mW) from raw counters."""
    from repro.core.params import CMD_ACT, CMD_PRE, CMD_RD, CMD_REF, CMD_WR

    c = {k: int(v) for k, v in zip(
        ["nop", "act", "rd", "wr", "pre", "ref", "srefe", "srefx"],
        list(counters["cmd_counts"]),
    )}
    cmd_nj = (
        c["act"] * pcfg.e_act_nj
        + c["pre"] * pcfg.e_pre_nj
        + c["rd"] * pcfg.e_rd_nj
        + c["wr"] * pcfg.e_wr_nj
        + c["ref"] * pcfg.e_ref_nj
    )
    ns_per_cycle = 1.0 / pcfg.clock_ghz
    bg_nj = (
        float(counters["active_cycles"]) * pcfg.p_act_standby_mw
        + float(counters["idle_cycles"]) * pcfg.p_pre_standby_mw
        + float(counters["sref_cycles"]) * pcfg.p_sref_mw
    ) * 1e-3 * ns_per_cycle  # mW * ns = pJ; *1e-3 -> nJ
    total_cycles = (
        float(counters["active_cycles"])
        + float(counters["idle_cycles"])
        + float(counters["sref_cycles"])
    )
    total_nj = cmd_nj + bg_nj
    avg_mw = 0.0
    if total_cycles > 0:
        avg_mw = total_nj / (total_cycles * ns_per_cycle) * 1e3
    return {
        "command_energy_uj": cmd_nj * 1e-3,
        "background_energy_uj": bg_nj * 1e-3,
        "total_energy_uj": total_nj * 1e-3,
        "avg_power_mw_per_bank": avg_mw,
        "counts": c,
    }
