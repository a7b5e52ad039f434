"""MemorySim configuration: static topology vs runtime parameters.

The configuration layer is split along the compile boundary:

* :class:`Topology` — everything that determines array *shapes* or the
  *structure* of the compiled program (channel/rank/bankgroup/bank counts,
  queue capacities, backing-store size, FSM backend). Frozen + hashable, it
  is the only static ``jax.jit`` argument; two configs with the same
  topology share one compiled XLA program.

* :class:`RuntimeParams` — every JEDEC timing parameter of the paper's
  Table 1 plus the page policy and scheduling policy, lowered from strings
  to int flags. It is a NamedTuple *pytree* of traced int32 scalars, so a
  whole (timing x policy x refresh x queue-depth) sweep grid runs through a
  single compiled program — only the data changes per lane.

* :class:`MemSimConfig` — the historical facade (Topology + all runtime
  fields in one frozen dataclass). Every existing call site keeps working;
  ``cfg.topology()`` / ``cfg.runtime()`` perform the split at the API edge.

The paper's Table 1 gives the timing parameters MemorySim implements; values
here default to the paper's published numbers. Two parameters the paper's
table omits but its FSM requires are added and documented:

  * ``tCL``  — READ/WRITE data-return latency (the duration of the RW_WAIT
    state; the paper's READ-ack delay is unspecified, we use the JEDEC-typical
    CAS latency equal to tRCD).
  * ``tXS``  — self-refresh exit latency (the paper has an SREF EXIT command
    but gives no duration).
  * ``tRTW`` — read->write turnaround (the table's tCCDL note says the write
    gap "depends on previous op"; we use a distinct parameter defaulting to
    tCCDL).

JEDEC bank-group timing (``Topology.timing_model == "jedec"``, DDR4/DDR5):
the Table-1 gaps ``tRRDL``, ``tCCDL`` and ``tWTR`` become the
same-bank-group (L) gaps, ``tRRDS``, ``tCCDS`` and ``tWTRS`` the
different-bank-group (S) gaps, and each bank gains a precharge gate from
``tRAS`` (ACT->PRE), ``tRTP`` (RD->PRE) and ``tWTP`` (WR->PRE, i.e.
CWL + BL/2 + tWR command to command). Under the default ``"table1"`` model
those six fields are carried in the parameter vector and read by nothing.

Address mapping (paper §5.2)::

    address <- {remaining_bits, rank_idx, bankgroup_idx, bank_idx}

i.e. bank index occupies the least-significant bits, then bankgroup, then
rank; everything above is row/column ("remaining").
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple


def _log2(x: int) -> int:
    assert x > 0 and (x & (x - 1)) == 0, f"{x} must be a power of two"
    return int(math.log2(x))


# Policy flags: RuntimeParams lowers the policy strings to int32 data so a
# single compiled program selects behaviour with jnp.where/lax.cond.
PAGE_CLOSED, PAGE_OPEN = 0, 1
SCHED_FCFS, SCHED_FRFCFS = 0, 1
PAGE_POLICIES = {"closed": PAGE_CLOSED, "open": PAGE_OPEN}
SCHED_POLICIES = {"fcfs": SCHED_FCFS, "frfcfs": SCHED_FRFCFS}
FSM_BACKENDS = ("jnp", "pallas", "fused")
#: "table1": the paper's rank-scoped windows; "jedec": bank-group (S/L)
#: windows and a per-bank precharge gate (see the module docstring)
TIMING_MODELS = ("table1", "jedec")


@dataclasses.dataclass(frozen=True)
class Topology:
    """Static shape-determining configuration — the only ``jax.jit`` static.

    Frozen + hashable; everything here sets an array shape (bank counts,
    queue capacities, backing-store size) or the op structure of the
    compiled program (FSM backend). All timing values and policies live in
    :class:`RuntimeParams` and are traced.
    """

    # ---- topology -------------------------------------------------------
    channels: int = 1
    ranks: int = 2
    bankgroups: int = 4
    banks_per_group: int = 4
    column_bits: int = 6          # low "remaining" bits that index within a row

    # ---- memory tiers (DRAM + CXL expander) ------------------------------
    # A tier is a partition of the channel axis: the first ``dram_channels``
    # channels are tier 0 (direct DRAM), the last ``cxl_channels`` are
    # tier 1 (CXL-attached expander). Each tier carries its own
    # RuntimeParams row (latency adders, narrower link, independent
    # refresh/SREF) — see ``tiered_params``. ``tiers == 1`` is the
    # homogeneous single-pool configuration and compiles to exactly the
    # pre-tier program.
    tiers: int = 1
    cxl_channels: int = 0

    # ---- queue capacities (static buffer shapes; the *runtime* depth is a
    # traced limit — see bench.reference_jedec.queues) --------------------------------
    queue_size: int = 128         # global reqQueue depth == per-bank queue depth
    resp_queue_size: int = 64

    # ---- data correctness -------------------------------------------------
    mem_words: int = 1 << 16      # word-addressable backing store size

    # ---- backend ------------------------------------------------------------
    # "jnp": pure-jnp FSM update (CPU default). "pallas": the TPU kernel in
    # repro.kernels.bank_fsm (interpret mode on CPU — slow inside long scans,
    # meant for TPU deployment; equivalence is enforced by the kernel tests).
    # "fused": one Pallas call per executed cycle covering FSM update, queue
    # head peek/pop bookkeeping, response push + ready&valid gating, both
    # round-robin arbiters, DRAM timing-window updates, and the event-horizon
    # bound (repro.kernels.bank_fsm.fused).
    fsm_backend: str = "jnp"

    # ---- timing model ----------------------------------------------------
    # "table1": the paper's rank-scoped windows, today's program. "jedec":
    # [R, BG] bank-group registers beside the rank ones, the S/L windows
    # and a per-bank precharge gate — extra state and ops that only this
    # model traces (Python branches on this static field).
    timing_model: str = "table1"

    def __post_init__(self):
        if self.fsm_backend not in FSM_BACKENDS:
            raise ValueError(
                f"fsm_backend={self.fsm_backend!r} not in {FSM_BACKENDS}")
        if self.timing_model not in TIMING_MODELS:
            raise ValueError(
                f"timing_model={self.timing_model!r} not in {TIMING_MODELS}")

    @property
    def jedec(self) -> bool:
        """Whether the bank-group windows and precharge gate are modelled."""
        return self.timing_model == "jedec"

    # ---- derived ----------------------------------------------------------
    @property
    def banks_per_rank(self) -> int:
        return self.bankgroups * self.banks_per_group

    @property
    def banks_per_channel(self) -> int:
        return self.ranks * self.banks_per_rank

    @property
    def num_banks(self) -> int:
        """Total flattened bank count B = C * R * BG * BA."""
        return self.channels * self.banks_per_channel

    @property
    def num_ranks(self) -> int:
        """Total flattened rank count (channels * ranks)."""
        return self.channels * self.ranks

    @property
    def bank_bits(self) -> int:
        return _log2(self.banks_per_group)

    @property
    def bankgroup_bits(self) -> int:
        return _log2(self.bankgroups)

    @property
    def rank_bits(self) -> int:
        return _log2(self.ranks)

    @property
    def channel_bits(self) -> int:
        return _log2(self.channels)

    @property
    def dram_channels(self) -> int:
        """Channels in tier 0 (direct DRAM)."""
        return self.channels - self.cxl_channels

    @property
    def tier_split_bank(self) -> int:
        """Index of the first tier-1 (CXL) flattened bank; equals
        ``num_banks`` when there is no second tier."""
        return self.dram_channels * self.banks_per_channel

    @property
    def tier_split_rank(self) -> int:
        """Index of the first tier-1 (CXL) flattened rank."""
        return self.dram_channels * self.ranks

    @property
    def addr_low_bits(self) -> int:
        """Bits consumed by {channel, rank, bankgroup, bank}."""
        return self.bank_bits + self.bankgroup_bits + self.rank_bits + self.channel_bits

    def topology(self) -> "Topology":
        """The pure static slice (identity for a plain Topology; strips the
        runtime fields off a :class:`MemSimConfig` facade so jit caching
        keys on shapes only)."""
        return Topology(**{f.name: getattr(self, f.name)
                           for f in dataclasses.fields(Topology)})

    def validate(self) -> "Topology":
        for f in ("channels", "ranks", "bankgroups", "banks_per_group"):
            v = getattr(self, f)
            if v <= 0 or (v & (v - 1)) != 0:
                raise ValueError(f"{f}={v} must be a power of two")
        if self.queue_size < 1:
            raise ValueError(f"queue_size={self.queue_size} must be >= 1")
        if self.resp_queue_size < 1:
            raise ValueError(
                f"resp_queue_size={self.resp_queue_size} must be >= 1")
        if self.tiers not in (1, 2):
            raise ValueError(f"tiers={self.tiers} must be 1 or 2 (DRAM, "
                             "or DRAM + CXL expander)")
        if self.tiers == 1 and self.cxl_channels != 0:
            raise ValueError(
                f"cxl_channels={self.cxl_channels} requires tiers=2")
        if self.tiers == 2:
            for f, v in (("cxl_channels", self.cxl_channels),
                         ("dram_channels", self.dram_channels)):
                if v <= 0 or (v & (v - 1)) != 0:
                    raise ValueError(
                        f"{f}={v} must be a power of two >= 1 when tiers=2 "
                        f"(channels={self.channels} is partitioned "
                        f"DRAM|CXL)")
        if self.jedec and self.fsm_backend == "pallas":
            raise ValueError(
                'timing_model="jedec" runs on the "jnp" and "fused" '
                'backends; the split "pallas" kernels know no bank-group '
                "windows")
        return self


class RuntimeParams(NamedTuple):
    """Traced runtime parameters: paper Table-1 timings + policy flags.

    A pytree of int32 scalars (or Python ints — coerced on trace). Because
    these are *data*, not static jit arguments, a whole parameter grid
    (timings x page policy x scheduler x refresh interval) shares one
    compiled XLA program; batch lanes simply carry different values. Policy
    strings are lowered to the ``PAGE_*`` / ``SCHED_*`` int flags.
    """

    tRP: int = 14                 # precharge period
    tFAW: int = 30                # four-activation window
    tRRDL: int = 6                # min cycles between two ACTs (same rank)
    tRCDRD: int = 14              # ACTIVATE -> READ delay
    tRCDWR: int = 14              # ACTIVATE -> WRITE delay
    tCCDL: int = 2                # gap between consecutive column commands
    tWTR: int = 8                 # WRITE -> READ turnaround
    tRFC: int = 260               # refresh cycle time / "deadline to start"
    tREFI: int = 3600             # refresh interval
    tCL: int = 14                 # column command data-return latency
    tXS: int = 10                 # self-refresh exit latency
    tRTW: int = 2                 # read -> write turnaround
    sref_idle_cycles: int = 1000  # idle cycles before SREF entry
    page_policy: int = PAGE_CLOSED
    sched_policy: int = SCHED_FCFS
    # ---- host-side tier placement (tiers=2 topologies; inert otherwise) --
    # Interleave granularity: addresses are split into 2^tier_interleave_log2
    # word blocks; block index b goes to CXL iff
    # ``b % 2^tier_cxl_frac_log2 == 2^tier_cxl_frac_log2 - 1`` — CXL owns 1
    # of every 2^k blocks, i.e. a DRAM:CXL capacity split of (2^k - 1):1.
    # Both are traced data, so placement policy is a sweep/lane axis. They
    # must be tier-uniform (the front-end resolves them as scalars).
    tier_interleave_log2: int = 6
    tier_cxl_frac_log2: int = 1
    # ---- JEDEC bank-group windows (timing_model="jedec"; inert otherwise)
    # the defaults make "jedec" reproduce "table1": S gaps equal to the
    # Table-1 gaps and a precharge gate that never holds
    tRRDS: int = 6                # ACT -> ACT, different bank group
    tCCDS: int = 2                # column -> column, different bank group
    tWTRS: int = 8                # WRITE -> READ, different bank group
    tRAS: int = 1                 # ACT -> PRE, same bank
    tRTP: int = 1                 # READ -> PRE, same bank
    tWTP: int = 1                 # WRITE -> PRE, same bank (CWL+BL/2+tWR)

    @classmethod
    def from_config(cls, cfg: "MemSimConfig") -> "RuntimeParams":
        # field-name driven (policies lowered to flags) so a parameter
        # added to both RuntimeParams and MemSimConfig is picked up
        # automatically instead of silently falling back to the default
        kw = {f: getattr(cfg, f) for f in cls._fields
              if f not in ("page_policy", "sched_policy")}
        return cls(page_policy=PAGE_POLICIES[cfg.page_policy],
                   sched_policy=SCHED_POLICIES[cfg.sched_policy], **kw)

    def pack(self):
        """Flatten to an int32 ``[NUM_RUNTIME_PARAMS, 1]`` column vector —
        the kernel-ABI form the Pallas bank-FSM backend consumes."""
        import jax.numpy as jnp

        return jnp.stack(
            [jnp.asarray(v, jnp.int32).reshape(()) for v in self]
        ).reshape(len(self._fields), 1)

    @classmethod
    def unpack(cls, vec) -> "RuntimeParams":
        """Inverse of :meth:`pack` (``vec`` int32 [NP, 1] or [NP])."""
        flat = vec.reshape(len(cls._fields))
        return cls(*[flat[i] for i in range(len(cls._fields))])

    @classmethod
    def stack(cls, rps) -> "RuntimeParams":
        """Stack a sequence of RuntimeParams on a leading batch axis (the
        vmap-lane form used by the batched engine)."""
        import jax.numpy as jnp

        return cls(*[
            jnp.asarray([jnp.asarray(getattr(rp, f), jnp.int32) for rp in rps])
            for f in cls._fields])

    def apply_to(self, cfg: "MemSimConfig") -> "MemSimConfig":
        """Inverse of :meth:`from_config`: ``cfg`` with this parameter
        point substituted (flags raised back to the policy strings), so
        results simulated under a ``params=`` override carry an accurate
        config label. Returns ``cfg`` unchanged if any leaf is traced."""
        import dataclasses as _dc

        try:
            vals = {f: int(getattr(self, f)) for f in self._fields}
        except Exception:  # traced leaves cannot be concretized host-side
            return cfg
        vals["page_policy"] = {v: k for k, v in
                               PAGE_POLICIES.items()}[vals["page_policy"]]
        vals["sched_policy"] = {v: k for k, v in
                                SCHED_POLICIES.items()}[vals["sched_policy"]]
        return _dc.replace(cfg, **vals)


NUM_RUNTIME_PARAMS = len(RuntimeParams._fields)
#: field -> row index of the packed kernel-ABI vector
RP_INDEX = {name: i for i, name in enumerate(RuntimeParams._fields)}

#: fields that must be equal across tiers: the front-end/glue resolves them
#: as machine-global scalars (placement decode, queue promotion policy)
TIER_UNIFORM_FIELDS = ("page_policy", "sched_policy",
                       "tier_interleave_log2", "tier_cxl_frac_log2")


def tiered_params(*tier_rps) -> "RuntimeParams":
    """Stack one :class:`RuntimeParams` point per memory tier (DRAM first,
    then the CXL expander) into the tier-stacked form the engines consume
    for ``tiers > 1`` topologies: every leaf becomes int32[T].

    Fields in :data:`TIER_UNIFORM_FIELDS` must agree across tiers — they
    are resolved as machine-global scalars by the front-end (placement
    decode) and queue glue (FR-FCFS promotion), not per bank.
    """
    if len(tier_rps) < 2:
        raise ValueError("tiered_params needs one RuntimeParams per tier "
                         f"(>= 2), got {len(tier_rps)}")
    for f in TIER_UNIFORM_FIELDS:
        vals = []
        for rp in tier_rps:
            try:
                vals.append(int(getattr(rp, f)))
            except (TypeError, ValueError):  # traced leaf: caller owns it
                vals = None
                break
        if vals is not None and len(set(vals)) > 1:
            raise ValueError(
                f"{f} must be tier-uniform (resolved as a machine-global "
                f"scalar), got {vals} across tiers")
    return RuntimeParams.stack(tier_rps)


def tier_of_bank(topo: "Topology"):
    """Static int32[B] tier index of every flattened bank (numpy)."""
    import numpy as np

    ch = np.arange(topo.num_banks, dtype=np.int32) // topo.banks_per_channel
    return (ch >= topo.dram_channels).astype(np.int32)


def rp_for_banks(topo: "Topology", rp: "RuntimeParams") -> "RuntimeParams":
    """Resolve a (possibly tier-stacked) parameter point to per-bank form.

    For ``topo.tiers == 1`` this is the identity — the compiled graph is
    untouched. For tiered topologies every [T] leaf is gathered through the
    static bank->tier map to [B]; scalar leaves (a tier-uniform point) pass
    through unchanged and broadcast as before.
    """
    if topo.tiers == 1:
        return rp
    import jax.numpy as jnp

    idx = jnp.asarray(tier_of_bank(topo))

    def leaf(v):
        a = jnp.asarray(v, jnp.int32)
        return a if a.ndim == 0 else a[idx]

    return RuntimeParams(*[leaf(v) for v in rp])

#: sentinel boundary for "no further segment" / schedule padding (plain int
#: on purpose — a module-level jnp constant materialized during tracing
#: would leak that trace's context into later traces). Matches the engine's
#: event-horizon infinity so the two mins compose.
SCHEDULE_INF = 0x3FFFFFFF


class ParamSchedule(NamedTuple):
    """Piecewise-constant time-varying :class:`RuntimeParams` — DVFS,
    thermal throttling and refresh-rate stepping as a first-class layer.

    ``boundaries[s]`` is the first cycle of segment ``s`` (sorted strictly
    increasing, ``boundaries[0] == 0``); ``values`` is a
    ``RuntimeParams.stack``-ed pytree whose leaves carry one entry per
    segment. Both are traced int32 *data*: every schedule of a given
    segment count ``S`` shares one compiled XLA program, and a whole
    schedule sweep runs as batch lanes of a single program (only the
    boundary/value arrays differ per lane).

    The single resolver every consumer reads through is
    :meth:`params_at`: the parameters governing cycle ``c`` are
    ``values[segment_at(c)]``. A constant run is the degenerate ``S == 1``
    schedule (:meth:`constant`), which resolves with zero overhead — the
    engines accept a bare :class:`RuntimeParams` anywhere and lift it via
    :func:`as_schedule`, so no API breaks.

    Exactness contract: per-cycle reference semantics re-resolve
    ``params_at(schedule, cycle)`` every cycle; WAIT timers latch their
    duration from the params active at the grant cycle and merely count
    down across boundaries (real controllers do the same — an in-flight
    command completes at its issued timing). The event-horizon engine caps
    every skip at the next segment boundary, so each closed-form bound is
    evaluated under the segment it covers and stays bit-exact.

    Schedules with fewer segments than a batch requires are padded by
    :meth:`pad_to`: padding rows repeat the last segment's values with a
    ``SCHEDULE_INF`` boundary, so they are never active and never alter
    :meth:`segment_at` / :meth:`next_boundary`.
    """

    boundaries: "jnp.ndarray"     # int32[S] (or [L, S] when lane-stacked)
    values: RuntimeParams         # each leaf int32[S] (or [L, S])

    # ---- static shape ----------------------------------------------------
    @property
    def num_segments(self) -> int:
        """Segment count S — an array *shape*, static per compiled program."""
        import numpy as np

        return int(np.shape(self.boundaries)[-1])

    @property
    def num_tiers(self) -> int:
        """Memory-tier count T — an array *shape*, static per compiled
        program. A leaf is tier-stacked iff it carries one trailing axis
        beyond the boundaries' segment axis (``[.., S, T]`` vs ``[.., S]``);
        an untier-ed schedule reports 1."""
        import numpy as np

        bnd_nd = len(np.shape(self.boundaries))
        t = 1
        for v in self.values:
            shape = np.shape(v)
            if len(shape) == bnd_nd + 1:
                t = max(t, int(shape[-1]))
        return t

    # ---- construction ----------------------------------------------------
    @classmethod
    def constant(cls, rp: "RuntimeParams") -> "ParamSchedule":
        """The degenerate S=1 schedule: ``rp`` for the whole run."""
        import jax.numpy as jnp

        return cls(boundaries=jnp.zeros((1,), jnp.int32),
                   values=RuntimeParams.stack([rp]))

    @classmethod
    def from_segments(cls, segments) -> "ParamSchedule":
        """Build from ``[(start_cycle, RuntimeParams), ...]`` and validate
        (boundaries sorted/unique/starting at 0, every segment through the
        shared :func:`runtime_constraint_violations` predicate)."""
        import jax.numpy as jnp

        if not segments:
            raise ValueError("ParamSchedule needs at least one segment")
        starts = [int(s) for s, _ in segments]
        rps = [rp for _, rp in segments]
        return cls(boundaries=jnp.asarray(starts, jnp.int32),
                   values=RuntimeParams.stack(rps)).validate()

    # ---- the ONE resolver ------------------------------------------------
    def segment_at(self, cycle):
        """Index of the segment governing ``cycle`` (traced int32)."""
        import jax.numpy as jnp

        if self.num_segments == 1:
            return jnp.int32(0)
        b = jnp.asarray(self.boundaries, jnp.int32)
        c = jnp.asarray(cycle, jnp.int32)
        return (jnp.sum((c >= b).astype(jnp.int32)) - 1).astype(jnp.int32)

    def params_at(self, cycle) -> "RuntimeParams":
        """The :class:`RuntimeParams` governing ``cycle`` — the single
        resolver every consumer (stepper, event bounds, kernels) reads
        through. S=1 resolves statically (zero runtime cost)."""
        import jax.numpy as jnp

        if self.num_segments == 1:
            return RuntimeParams(
                *[jnp.asarray(v, jnp.int32)[0] for v in self.values])
        seg = self.segment_at(cycle)
        return RuntimeParams(
            *[jnp.asarray(v, jnp.int32)[seg] for v in self.values])

    def next_boundary(self, cycle):
        """First segment boundary strictly after ``cycle``
        (``SCHEDULE_INF`` when none): the event the horizon engine must
        min in so no skip crosses an operating-point change."""
        import jax.numpy as jnp

        if self.num_segments == 1:
            return jnp.int32(SCHEDULE_INF)
        b = jnp.asarray(self.boundaries, jnp.int32)
        c = jnp.asarray(cycle, jnp.int32)
        return jnp.min(jnp.where(b > c, b, SCHEDULE_INF)).astype(jnp.int32)

    # ---- kernel ABI ------------------------------------------------------
    def pack(self):
        """Flatten to the packed kernel ABI: ``(boundaries int32[S, 1],
        values int32[T*S, NP])`` — the schedule-aware generalization of
        :meth:`RuntimeParams.pack` the Pallas bank-FSM kernels consume
        (they resolve the active segment in-kernel).

        The values matrix is tier-major: row ``t*S + s`` is tier ``t``'s
        segment ``s``. A single-tier schedule (the historical case) is the
        ``T == 1`` degenerate layout — identical bytes to the pre-tier ABI,
        and the kernels' single-tier path reads it with zero extra work."""
        import jax.numpy as jnp

        s = self.num_segments
        t = self.num_tiers
        if t == 1:
            vals = jnp.stack(
                [jnp.asarray(v, jnp.int32).reshape(s) for v in self.values],
                axis=1)
        else:
            # broadcast every leaf to [S, T], transpose tier-major
            vals = jnp.stack(
                [jnp.broadcast_to(
                    jnp.asarray(v, jnp.int32).reshape(
                        (s, -1)), (s, t)).T.reshape(t * s)
                 for v in self.values],
                axis=1)
        return jnp.asarray(self.boundaries, jnp.int32).reshape(s, 1), vals

    @classmethod
    def unpack(cls, bounds, vals) -> "ParamSchedule":
        """Inverse of :meth:`pack` (``bounds`` [S, 1] or [S], ``vals``
        [T*S, NP] tier-major)."""
        s = bounds.reshape(-1).shape[0]
        t = vals.shape[0] // s
        if t == 1:
            leaves = [vals[:, i] for i in range(NUM_RUNTIME_PARAMS)]
        else:
            cube = vals.reshape(t, s, NUM_RUNTIME_PARAMS)
            leaves = [cube[:, :, i].T for i in range(NUM_RUNTIME_PARAMS)]
        return cls(boundaries=bounds.reshape(s),
                   values=RuntimeParams(*leaves))

    # ---- batching --------------------------------------------------------
    def pad_to(self, s: int) -> "ParamSchedule":
        """Pad to ``s`` segments with inert rows (boundary
        ``SCHEDULE_INF``, values repeating the last real segment) so
        heterogeneous schedules can share one compiled program."""
        import jax.numpy as jnp

        cur = self.num_segments
        if cur == s:
            return self
        if cur > s:
            raise ValueError(f"cannot pad {cur} segments down to {s}")
        extra = s - cur
        b = jnp.concatenate([
            jnp.asarray(self.boundaries, jnp.int32).reshape(cur),
            jnp.full((extra,), SCHEDULE_INF, jnp.int32)])

        def pad_leaf(v):
            a = jnp.asarray(v, jnp.int32)
            if a.ndim == 2:        # tier-stacked [S, T]
                return jnp.concatenate(
                    [a, jnp.broadcast_to(a[-1], (extra, a.shape[1]))])
            a = a.reshape(cur)
            return jnp.concatenate(
                [a, jnp.broadcast_to(a[-1], (extra,))])

        vals = RuntimeParams(*[pad_leaf(v) for v in self.values])
        return ParamSchedule(boundaries=b, values=vals)

    @classmethod
    def stack(cls, scheds) -> "ParamSchedule":
        """Stack schedules on a leading lane axis (padding each to the
        common segment count) — the vmap-lane form of the batched engine."""
        import jax.numpy as jnp

        scheds = list(scheds)
        s_max = max(sc.num_segments for sc in scheds)
        padded = [sc.pad_to(s_max) for sc in scheds]
        return cls(
            boundaries=jnp.stack(
                [jnp.asarray(sc.boundaries, jnp.int32) for sc in padded]),
            values=RuntimeParams(*[
                jnp.stack([jnp.asarray(getattr(sc.values, f), jnp.int32)
                           for sc in padded])
                for f in RuntimeParams._fields]))

    # ---- validation / labelling -----------------------------------------
    def segment(self, s: int) -> "RuntimeParams":
        """Segment ``s``'s parameter point (host-side indexing)."""
        import jax.numpy as jnp

        return RuntimeParams(
            *[jnp.asarray(v, jnp.int32)[s] for v in self.values])

    def validate(self, timing_model: str = "table1") -> "ParamSchedule":
        """Host-side validation: boundaries sorted, unique, starting at
        cycle 0 (``SCHEDULE_INF`` padding rows exempt, but only as a
        suffix), and every real segment's values through the same
        :func:`runtime_constraint_violations` predicate — so a bad
        schedule segment fails with the same ValueError text as config
        construction. Traced leaves (uninspectable host-side) skip their
        checks; the caller inside the trace owns those."""
        import numpy as np

        bad = []
        try:
            bounds = [int(x) for x in
                      np.asarray(self.boundaries).reshape(-1)]
        except Exception:  # traced boundaries
            bounds = None
        n_real = self.num_segments
        if bounds is not None:
            real = [b for b in bounds if b < SCHEDULE_INF]
            n_real = len(real)
            if len(real) != len(bounds) and any(
                    b < SCHEDULE_INF for b in bounds[n_real:]):
                bad.append("schedule padding rows (boundary >= "
                           f"{SCHEDULE_INF}) must form a suffix")
            if not real:
                bad.append("schedule needs at least one real segment "
                           "(boundary below the padding sentinel)")
            elif real[0] != 0:
                bad.append(f"schedule boundaries must start at cycle 0, "
                           f"got {real[0]}")
            for a, b in zip(real, real[1:]):
                if b <= a:
                    bad.append("schedule boundaries must be sorted and "
                               f"unique (strictly increasing): {a} then {b}")
        t_count = self.num_tiers
        for s in range(n_real):
            for ti in range(t_count):
                vals = {}
                for f in RuntimeParams._fields:
                    try:
                        arr = np.asarray(getattr(self.values, f))
                        if arr.ndim >= 2:     # tier-stacked [S, T]
                            vals[f] = int(arr[s, min(ti, arr.shape[1] - 1)])
                        else:                 # tier-uniform [S]
                            vals[f] = int(arr.reshape(-1)[s])
                    except Exception:  # traced leaf
                        vals[f] = None
                # a one-segment single-tier (constant) schedule keeps the
                # exact config-construction error text; otherwise name the
                # segment/tier
                prefix = ""
                if n_real > 1:
                    prefix = f"schedule segment {s}: "
                if t_count > 1:
                    prefix += f"tier {ti}: "
                bad.extend(prefix + m for m in
                           runtime_constraint_violations(vals, timing_model))
            for f in TIER_UNIFORM_FIELDS:
                try:
                    arr = np.asarray(getattr(self.values, f))
                except Exception:
                    continue
                if arr.ndim >= 2 and len(set(
                        int(x) for x in arr[s].reshape(-1))) > 1:
                    bad.append(
                        f"{f} must be tier-uniform (resolved as a "
                        f"machine-global scalar), got "
                        f"{[int(x) for x in arr[s].reshape(-1)]} across "
                        f"tiers")
        if bad:
            raise ValueError("; ".join(bad))
        return self

    def apply_to(self, cfg: "MemSimConfig") -> "MemSimConfig":
        """Label helper: a schedule with exactly one *real* segment
        (padding rows don't count) labels like its constant point
        (:meth:`RuntimeParams.apply_to`); a genuinely time-varying
        schedule cannot be represented by a static config and returns
        ``cfg`` unchanged (as do traced boundaries)."""
        import numpy as np

        try:
            bounds = np.asarray(self.boundaries).reshape(-1)
            n_real = int((bounds < SCHEDULE_INF).sum())
        except Exception:  # traced host-side-uninspectable boundaries
            return cfg
        if n_real == 1:
            return self.segment(0).apply_to(cfg)
        return cfg


def as_schedule(params) -> "ParamSchedule":
    """Lift ``params`` to the canonical :class:`ParamSchedule` form: a
    bare :class:`RuntimeParams` becomes the degenerate S=1 schedule, a
    schedule passes through — the no-API-break seam every ``params=``
    entry point funnels through."""
    if isinstance(params, ParamSchedule):
        return params
    if isinstance(params, RuntimeParams):
        return ParamSchedule.constant(params)
    raise TypeError(
        f"params must be RuntimeParams or ParamSchedule, got "
        f"{type(params).__name__}")

#: runtime fields that must be strictly positive: a zero or negative timing
#: value would make a WAIT state instantaneous (or run its timer negative)
#: and break every closed-form skip bound in the engine.
POSITIVE_RUNTIME_FIELDS = tuple(
    f for f in RuntimeParams._fields
    if f not in ("page_policy", "sched_policy",
                 "tier_interleave_log2", "tier_cxl_frac_log2"))


#: (S gap, L gap) pairs: a different-bank-group gap never exceeds its
#: same-bank-group twin
JEDEC_GAP_PAIRS = (("tRRDS", "tRRDL"), ("tCCDS", "tCCDL"), ("tWTRS", "tWTR"))


def runtime_constraint_violations(vals, timing_model: str = "table1") -> list:
    """Cross-field constraints on a runtime parameter point, shared by
    :meth:`MemSimConfig.validate` (config construction) and the engine's
    ``params=`` override path (``engine._rp_i32``), so both fail with the
    same message for the same bad point.

    ``vals`` maps every :class:`RuntimeParams` field (policies as int
    flags) to an int, or to ``None`` for a traced leaf that cannot be
    inspected host-side — constraints with an unknown operand are skipped
    (the caller inside the trace owns those). Returns the list of
    violation messages, empty when the point is valid. Under
    ``timing_model="jedec"`` each S gap must not exceed its L gap.
    """
    def known(*fields):
        return all(vals.get(f) is not None for f in fields)

    out = []
    for f in POSITIVE_RUNTIME_FIELDS:
        if known(f) and vals[f] < 1:
            out.append(f"{f}={vals[f]} must be >= 1")
    if known("tREFI", "tRFC") and vals["tREFI"] <= vals["tRFC"]:
        out.append(
            f"tREFI={vals['tREFI']} (refresh interval) must exceed "
            f"tRFC={vals['tRFC']} (refresh cycle time)")
    if known("tFAW", "tRRDL") and vals["tFAW"] < vals["tRRDL"]:
        out.append(
            f"tFAW={vals['tFAW']} (four-activation window) must be >= "
            f"tRRDL={vals['tRRDL']} (ACT-to-ACT gap)")
    if known("page_policy") and vals["page_policy"] not in (PAGE_CLOSED,
                                                            PAGE_OPEN):
        out.append(
            f"page_policy flag {vals['page_policy']} not in "
            f"{{{PAGE_CLOSED} (closed), {PAGE_OPEN} (open)}}")
    if known("sched_policy") and vals["sched_policy"] not in (SCHED_FCFS,
                                                              SCHED_FRFCFS):
        out.append(
            f"sched_policy flag {vals['sched_policy']} not in "
            f"{{{SCHED_FCFS} (fcfs), {SCHED_FRFCFS} (frfcfs)}}")
    if known("tier_interleave_log2") and not (
            0 <= vals["tier_interleave_log2"] <= 24):
        out.append(
            f"tier_interleave_log2={vals['tier_interleave_log2']} must be "
            f"in [0, 24] (word-block interleave granularity)")
    if known("tier_cxl_frac_log2") and not (
            1 <= vals["tier_cxl_frac_log2"] <= 20):
        out.append(
            f"tier_cxl_frac_log2={vals['tier_cxl_frac_log2']} must be in "
            f"[1, 20] (CXL owns 1 of every 2^k interleave blocks)")
    if timing_model == "jedec":
        for short, long in JEDEC_GAP_PAIRS:
            if known(short, long) and vals[short] > vals[long]:
                out.append(
                    f"{short}={vals[short]} (different bank group) must be "
                    f"<= {long}={vals[long]} (same bank group)")
    return out


@dataclasses.dataclass(frozen=True)
class MemSimConfig(Topology):
    """Back-compat facade: Topology + runtime parameters in one object.

    Frozen + hashable so legacy call sites can still pass it as a static
    ``jax.jit`` argument; the engines split it at the API edge via
    :meth:`topology` / :meth:`runtime` so the compiled programs key on the
    static slice only.
    """

    # ---- timing parameters (paper Table 1 values) ------------------------
    tRP: int = 14                 # precharge period
    tFAW: int = 30                # four-activation window
    tRRDL: int = 6                # min cycles between two ACTs (same rank)
    tRCDRD: int = 14              # ACTIVATE -> READ delay
    tRCDWR: int = 14              # ACTIVATE -> WRITE delay
    tCCDL: int = 2                # gap between consecutive column commands
    tWTR: int = 8                 # WRITE -> READ turnaround
    tRFC: int = 260               # refresh cycle time / "deadline to start"
    tREFI: int = 3600             # refresh interval
    # ---- additions documented in the module docstring -------------------
    tCL: int = 14                 # column command data-return latency
    tXS: int = 10                 # self-refresh exit latency
    tRTW: int = 2                 # read -> write turnaround

    # ---- self refresh (paper §5.2.3) -------------------------------------
    sref_idle_cycles: int = 1000  # idle cycles before SREF entry

    # ---- page policy -------------------------------------------------------
    # "closed" = the paper's policy (every request ACT->RW->PRE).
    # "open"   = the paper's stated future work ("per-bank read caching"):
    # rows stay open, row hits skip ACT+PRE, conflicts precharge first.
    page_policy: str = "closed"

    # ---- scheduling policy ---------------------------------------------------
    # "fcfs"   = in-order per-bank queues (the paper's scheduler).
    # "frfcfs" = first-ready FCFS (the DRAMSim3 feature the paper compares
    # against): the oldest row-hit is promoted to the head of each bank
    # queue, with a same-address dependency guard. Meaningful with
    # page_policy="open".
    sched_policy: str = "fcfs"

    # ---- tier placement (tiers=2 topologies; inert on a single tier) -----
    tier_interleave_log2: int = 6
    tier_cxl_frac_log2: int = 1

    # ---- JEDEC bank-group windows (timing_model="jedec") ----------------
    tRRDS: int = 6
    tCCDS: int = 2
    tWTRS: int = 8
    tRAS: int = 1
    tRTP: int = 1
    tWTP: int = 1

    def __post_init__(self):
        super().__post_init__()
        if self.page_policy not in PAGE_POLICIES:
            raise ValueError(
                f"page_policy={self.page_policy!r} not in "
                f"{sorted(PAGE_POLICIES)}")
        if self.sched_policy not in SCHED_POLICIES:
            raise ValueError(
                f"sched_policy={self.sched_policy!r} not in "
                f"{sorted(SCHED_POLICIES)}")

    def runtime(self) -> RuntimeParams:
        """The traced slice (policies lowered to int flags)."""
        return RuntimeParams.from_config(self)

    def validate(self) -> "MemSimConfig":
        Topology.validate(self)
        vals = {f: getattr(self, f) for f in RuntimeParams._fields
                if f not in ("page_policy", "sched_policy")}
        # __post_init__ guarantees the policy strings resolve
        vals["page_policy"] = PAGE_POLICIES[self.page_policy]
        vals["sched_policy"] = SCHED_POLICIES[self.sched_policy]
        bad = runtime_constraint_violations(vals, self.timing_model)
        if bad:
            raise ValueError("; ".join(bad))
        return self


# FSM states of the bank scheduler (paper Fig 2) --------------------------
# ISSUE states bid on the shared command bus; WAIT states hold a timer that
# the DRAM timing model counts down.
S_IDLE = 0
S_REF_ISSUE = 1
S_REF_WAIT = 2
S_SREF_ISSUE = 3
S_SREF = 4                        # parked in self refresh
S_SREF_EXIT_ISSUE = 5
S_SREF_EXIT_WAIT = 6
S_ACT_ISSUE = 7
S_ACT_WAIT = 8
S_RW_ISSUE = 9
S_RW_WAIT = 10
S_PRE_ISSUE = 11
S_PRE_WAIT = 12
S_RESP_PEND = 13                  # completion token awaiting response arbiter
NUM_STATES = 14

# DRAM commands on the shared bus ----------------------------------------
CMD_NOP = 0
CMD_ACT = 1
CMD_RD = 2
CMD_WR = 3
CMD_PRE = 4
CMD_REF = 5
CMD_SREF_ENTER = 6
CMD_SREF_EXIT = 7
NUM_CMDS = 8

DEFAULT_CONFIG = MemSimConfig()

# The Table-1 defaults are declared on both RuntimeParams (bare pytree
# construction) and the MemSimConfig facade; fail at import time if they
# ever drift apart instead of silently simulating with stale values.
if RuntimeParams() != RuntimeParams.from_config(DEFAULT_CONFIG):
    raise RuntimeError(
        "RuntimeParams field defaults drifted from MemSimConfig defaults: "
        f"{RuntimeParams()} != {RuntimeParams.from_config(DEFAULT_CONFIG)}")
