"""Jit'd wrapper for the bank-FSM kernel with padding + backend dispatch."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import Array

from repro.core.params import MemSimConfig, S_IDLE, Topology, as_schedule
from repro.kernels.bank_fsm.bank_fsm import (
    bank_event_bound_pallas,
    bank_fsm_step_pallas,
)
from repro.kernels.bank_fsm.ref import bank_event_bound_ref, bank_fsm_step_ref

# plain int, not a jnp array: this module is imported lazily from inside
# traced cycle loops, and a module-level jnp constant materialized during
# tracing would leak that trace's context into later traces
_FAR_FUTURE = 0x3FFFFFFF


def _block_b(b: int) -> int:
    """Bank-axis block width: clamp to the actual bank count so small
    topologies (e.g. 8 banks) don't pad 16x per call. ``b`` is a power of
    two (Topology.validate), so ``min(128, b)`` always divides the padded
    extent; the wrappers assert this."""
    return min(128, b)


def default_interpret() -> bool:
    """Pallas execution mode, chosen by the platform alone: the
    interpreter on CPU (which has no Mosaic lowering), the compiled kernel
    on TPU. Any other platform is an error — there is no fallback, so a
    kernel that Mosaic refuses fails the run instead of silently timing
    the interpreter. The result is a plain Python bool baked into the
    traced program as a static."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"no Pallas kernel path for platform {platform!r}: the bank-FSM "
        "kernels run compiled on TPU and interpreted on CPU only")


def _pad_banks(state: Array, inputs: Array, pop: Array, padded_b: int):
    b = state.shape[1]
    if b == padded_b:
        return state, inputs, pop
    extra = padded_b - b
    pad_state = jnp.zeros((10, extra), jnp.int32)
    pad_state = pad_state.at[0].set(S_IDLE)
    pad_state = pad_state.at[3].set(_FAR_FUTURE)  # never refresh
    pad_state = pad_state.at[7].set(-1)
    pad_state = pad_state.at[8].set(-1)           # no open row
    state = jnp.concatenate([state, pad_state], axis=1)
    inputs = jnp.concatenate([inputs, jnp.zeros((3, extra), jnp.int32)], axis=1)
    pop = jnp.concatenate([pop, jnp.zeros((4, extra), jnp.int32)], axis=1)
    return state, inputs, pop


def bank_event_bound(
    state: Array,    # [10, B] int32 packed BankState
    cycle: Array,    # scalar or [1,1] int32
    params,          # RuntimeParams (constant) or ParamSchedule
    use_pallas: bool = False,
    interpret: bool = True,
    topo: Optional[Topology] = None,
) -> Array:
    """Per-bank cycles-until-actionable on the packed ABI; returns
    int32[B]. ``params`` may be a constant :class:`RuntimeParams` (lifted
    to the S=1 schedule) or a :class:`ParamSchedule` — the kernel resolves
    the segment governing ``cycle`` in-kernel. The Pallas path pads the
    bank axis like :func:`bank_fsm_step` and slices the padded lanes back
    off, so both backends agree bank-for-bank with
    :func:`repro.core.bank_fsm.cycles_until_actionable` (enforced by the
    kernel tests). Callable from inside traced loops — no jit wrapper of
    its own, it inlines into the caller's program.

    ``topo`` is only needed for tiered topologies (``topo.tiers > 1``): it
    supplies the static DRAM/CXL bank split so per-tier params rows of the
    tier-major [T*S, NP] matrix resolve per bank. Omitted (or single-tier)
    it is the exact pre-tier path."""
    cycle2d = jnp.asarray(cycle, jnp.int32).reshape(1, 1)
    bounds, rp_mat = as_schedule(params).pack()
    if not use_pallas:
        return bank_event_bound_ref(state, rp_mat, bounds, cycle2d,
                                    topo=topo)[0]
    b = state.shape[1]
    block_b = _block_b(b)
    padded_b = ((b + block_b - 1) // block_b) * block_b
    assert padded_b % block_b == 0
    ps, _, _ = _pad_banks(state, jnp.zeros((3, b), jnp.int32),
                          jnp.zeros((4, b), jnp.int32), padded_b)
    tiers = 1 if topo is None else topo.tiers
    split = 0 if topo is None or tiers == 1 else topo.tier_split_bank
    bound = bank_event_bound_pallas(ps, rp_mat, bounds, cycle2d,
                                    block_b=block_b, interpret=interpret,
                                    tiers=tiers, tier_split=split)
    return bound[0, :b]


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def bank_fsm_step(
    cfg: Topology,   # Topology or the MemSimConfig facade (static)
    state: Array,    # [10, B] int32
    inputs: Array,   # [3, B] int32 0/1
    pop: Array,      # [4, B] int32
    cycle: Array,    # scalar or [1,1] int32
    use_pallas: bool = False,
    interpret: bool = True,
    params=None,     # RuntimeParams (constant) or ParamSchedule
) -> Tuple[Array, Array]:
    """One FSM clock edge. Returns (new_state [10,B], flags [3,B]).

    ``use_pallas=False`` runs the pure-jnp oracle (the simulator's default on
    CPU); ``use_pallas=True`` runs the Pallas kernel (``interpret=True`` for
    CPU validation, ``False`` on real TPUs).

    ``params`` carries the traced timing/policy values — a constant
    :class:`RuntimeParams` (lifted to the S=1 schedule) or a full
    :class:`ParamSchedule`, whose active segment the kernel resolves
    in-kernel from the packed ``[S, NP]`` matrix + ``[S, 1]`` boundary
    vector. When omitted they are lifted from ``cfg`` (which must then be
    the full :class:`MemSimConfig` facade). Passing them explicitly keeps
    them runtime data, so one compiled kernel serves a whole parameter
    sweep (and every schedule of the same segment count).
    """
    if params is None:
        if not isinstance(cfg, MemSimConfig):
            raise ValueError("params required when cfg is a bare Topology")
        params = cfg.runtime()
    topo = cfg.topology()
    cycle2d = jnp.asarray(cycle, jnp.int32).reshape(1, 1)
    bounds, rp_mat = as_schedule(params).pack()
    if not use_pallas:
        return bank_fsm_step_ref(topo, state, inputs, pop, rp_mat, bounds,
                                 cycle2d)
    b = state.shape[1]
    block_b = _block_b(b)
    padded_b = ((b + block_b - 1) // block_b) * block_b
    assert padded_b % block_b == 0
    ps, pi, pp = _pad_banks(state, inputs, pop, padded_b)
    new_state, flags = bank_fsm_step_pallas(
        topo, ps, pi, pp, rp_mat, bounds, cycle2d, block_b=block_b,
        interpret=interpret
    )
    return new_state[:, :b], flags[:, :b]
