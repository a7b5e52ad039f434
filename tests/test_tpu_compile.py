"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret mode on CPU runs every kernel, but never through Mosaic, the
TPU kernel compiler: a reshape across the lane axis or a relayout it
cannot do only shows up here, and so does a kernel XLA would have to
partition across chips. Each test lowers and compiles one kernel (or one
whole engine program) for one chip, or all four, of a ``v5e:2x2``
topology that is described, not attached, and asserts that the kernel is in the compiled program
(``tpu_custom_call``). Nothing runs; results are the interpret-mode tests'
job.

The topology is described inside a module fixture — never at import — so
under several pytest workers only the worker that runs this file loads
the TPU compiler library.
"""

from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MemSimConfig
from repro.core.params import NUM_RUNTIME_PARAMS, Topology
from repro.kernels.bank_fsm.fused import (
    NUM_BANK_ROWS_IN,
    NUM_SCAL_IN,
    fused_step_pallas,
)


@pytest.fixture(scope="module")
def chips():
    from jax.experimental import topologies

    # the TPU compiler otherwise writes its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topo.devices
    # drop every program traced for the described chip, so no later test
    # in this process can pick up a non-interpret trace
    jax.clear_caches()


@pytest.fixture(scope="module")
def one_chip(chips):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(chips[0])


def _sds(sharding, shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("channels,tiers,segments,lanes", [
    (2, 1, 1, 1),
    (2, 1, 1, 8),
    (2, 1, 3, 1),
    (2, 2, 1, 1),
], ids=["base", "lanes8", "segments3", "tiers2"])
def test_fused_kernel_compiles(one_chip, channels, tiers, segments, lanes):
    topo = Topology(channels=channels, tiers=tiers,
                    cxl_channels=1 if tiers == 2 else 0,
                    fsm_backend="fused")
    b = topo.num_banks
    args = (_sds(one_chip, (NUM_BANK_ROWS_IN, lanes, b)),
            _sds(one_chip, (4, lanes, topo.resp_queue_size)),
            _sds(one_chip, (lanes, tiers * segments * NUM_RUNTIME_PARAMS)),
            _sds(one_chip, (lanes, segments)),
            _sds(one_chip, (lanes, NUM_SCAL_IN + channels)))
    fn = jax.jit(functools.partial(fused_step_pallas, topo, interpret=False))
    _assert_kernel(fn.lower(*args).compile())


def test_split_fsm_step_compiles(one_chip):
    from repro.kernels.bank_fsm.bank_fsm import bank_fsm_step_pallas

    topo = Topology(channels=2)
    b = topo.num_banks
    args = (_sds(one_chip, (10, b)), _sds(one_chip, (3, b)),
            _sds(one_chip, (4, b)), _sds(one_chip, (1, NUM_RUNTIME_PARAMS)),
            _sds(one_chip, (1, 1)), _sds(one_chip, (1, 1)))
    fn = jax.jit(functools.partial(bank_fsm_step_pallas, topo,
                                   block_b=min(128, b), interpret=False))
    _assert_kernel(fn.lower(*args).compile())


def test_split_event_bound_compiles(one_chip):
    from repro.kernels.bank_fsm.bank_fsm import bank_event_bound_pallas

    b = Topology(channels=2).num_banks
    args = (_sds(one_chip, (10, b)), _sds(one_chip, (1, NUM_RUNTIME_PARAMS)),
            _sds(one_chip, (1, 1)), _sds(one_chip, (1, 1)))
    fn = jax.jit(functools.partial(bank_event_bound_pallas,
                                   block_b=min(128, b), interpret=False))
    _assert_kernel(fn.lower(*args).compile())


def test_fused_engine_compiles(one_chip, monkeypatch):
    """The whole single-lane event-horizon engine on the fused backend, at
    the decode-serving trace's size. The engine asks the platform whether
    to interpret; the test answers "TPU"."""
    from repro.core import engine, fused_step
    from repro.traces.llm_workload import decode_serving_trace

    monkeypatch.setattr(fused_step, "default_interpret", lambda: False)
    cfg = MemSimConfig(channels=2, queue_size=128, fsm_backend="fused")
    trace = decode_serving_trace()
    sched = engine._sched_i32(cfg.runtime())
    args = jax.tree_util.tree_map(
        lambda x: _sds(one_chip, np.shape(x)),
        (trace, jnp.int32(400_000), sched, jnp.int32(cfg.queue_size),
         jnp.int32(cfg.resp_queue_size)))
    # a fresh jit wrapper: its traces never mix with the engine's own
    fn = jax.jit(functools.partial(engine._run_skip_core, cfg.topology()))
    _assert_kernel(fn.lower(*args).compile())


def test_fused_batch_engine_compiles_over_four_chips(chips, monkeypatch):
    """The lane-batched fused engine with its lanes sharded over the four
    chips of the described host — the sweep path ``simulate_batch`` takes
    with several devices. XLA cannot partition a Pallas kernel, so this
    compiles only because the batch is split per device."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import ParamSchedule, engine, fused_step
    from repro.traces import BENCHMARKS

    monkeypatch.setattr(fused_step, "default_interpret", lambda: False)
    cfg = MemSimConfig(channels=2, queue_size=32, fsm_backend="fused")
    lanes = 8
    mesh = Mesh(np.asarray(chips), ("data",))
    tr = BENCHMARKS["trace_example"](n=40, gap=5)
    stacked, _ = engine.stack_traces([tr] * lanes)
    scheds = ParamSchedule.stack([engine._sched_i32(cfg.runtime())] * lanes)
    split, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    args = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.int32,
                                       sharding=split),
        (stacked, scheds, np.zeros(lanes), np.zeros(lanes)))
    nc = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
    compiled = engine._run_skip_batch_split_jit.lower(
        mesh, cfg.topology(), args[0], nc, *args[1:]).compile()
    _assert_kernel(compiled)


def _gather_sizes(text):
    """Output element counts of every gather in a compiled HLO text
    (fused computations included)."""
    sizes = []
    for dims in re.findall(r"= \w+\[([\d,]*)\]\{[^}]*\} gather\(", text):
        sizes.append(int(np.prod([int(d) for d in dims.split(",") if d])))
    return sizes


def test_batched_fused_engine_has_no_queue_wide_gather(one_chip, monkeypatch):
    """The lane-batched fused engine at the 64-lane sweep's shape (64
    lanes, 64 banks, queues of 128): the FR-FCFS promotion searches and
    swaps on the ring where it lies, so no gather reads every slot of
    every bank queue of every lane (an [L, B, Q] gather, which the chip
    runs at ~10 ns an element, every step)."""
    from repro.core import ParamSchedule, engine, fused_step
    from repro.traces import BENCHMARKS

    monkeypatch.setattr(fused_step, "default_interpret", lambda: False)
    cfg = MemSimConfig(channels=2, queue_size=128, fsm_backend="fused")
    topo = cfg.topology()
    lanes = 64
    tr = BENCHMARKS["trace_example"](n=40, gap=5)
    stacked, _ = engine.stack_traces([tr] * lanes)
    scheds = ParamSchedule.stack([engine._sched_i32(cfg.runtime())] * lanes)
    args = jax.tree_util.tree_map(
        lambda x: _sds(one_chip, np.shape(x)),
        (stacked, jnp.int32(100_000), scheds, np.zeros(lanes),
         np.zeros(lanes)))
    fn = jax.jit(functools.partial(engine._run_skip_batch_core, topo))
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    sizes = _gather_sizes(text)
    assert sizes, "no gather found: the HLO text pattern no longer matches"
    assert lanes * topo.num_banks * cfg.queue_size not in sizes, sizes


def _while_carry(jaxpr):
    """Shapes of the engine loop's carried values (the first while loop
    found, nested jaxprs searched)."""
    def find(j):
        for e in j.eqns:
            if e.primitive.name == "while":
                return e
            for v in e.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns") and (w := find(inner)):
                    return w
        return None

    loop = find(jaxpr.jaxpr)
    assert loop is not None
    return [tuple(v.aval.shape)
            for v in loop.params["body_jaxpr"].jaxpr.invars]


def _kernel_shapes(text):
    """``(operand shapes, result shapes)`` of the fused kernel's custom
    call in a compiled TPU HLO text."""
    line = next(ln for ln in text.splitlines()
                if "tpu_custom_call" in ln and "custom-call(" in ln)
    dims = re.compile(r"s32\[([\d,]*)\]")

    def shapes(s):
        return [tuple(int(d) for d in m.split(",") if d)
                for m in dims.findall(s)]

    results = line.split("custom-call(")[0]
    operands = line.split("operand_layout_constraints={")[1].split("}, ")[0]
    return shapes(operands), shapes(results)


def _engine(cfg, lanes):
    """The jitted engine and its argument shapes: the one-lane skip engine
    (``lanes`` None) or the lane-batched one."""
    from repro.core import ParamSchedule, engine
    from repro.traces import BENCHMARKS

    tr = BENCHMARKS["trace_example"](n=40, gap=5)
    topo = cfg.topology()
    if lanes is None:
        args = (tr, jnp.int32(100_000), engine._sched_i32(cfg.runtime()),
                jnp.int32(cfg.queue_size), jnp.int32(cfg.resp_queue_size))
        return functools.partial(engine._run_skip_core, topo), args
    stacked, _ = engine.stack_traces([tr] * lanes)
    scheds = ParamSchedule.stack([engine._sched_i32(cfg.runtime())] * lanes)
    args = (stacked, jnp.int32(100_000), scheds, np.zeros(lanes),
            np.zeros(lanes))
    return functools.partial(engine._run_skip_batch_core, topo), args


@pytest.mark.parametrize("lanes", [None, 64], ids=["one_lane", "batched"])
def test_table1_programs_keep_the_kernel_abi(one_chip, monkeypatch, lanes):
    """The DDR4 (``"table1"``) fused programs at ddr4-2ch's shape carry
    no bank-group register and keep today's kernel ABI: 23 bank rows in,
    22 out, 9 + 2C scalar columns out. The loop's only [R, 4]-shaped
    value is the tFAW window (``act_win``), which a bank-group register
    of DDR4's 4 groups would share its shape with."""
    from repro.core import fused_step
    from repro.kernels.bank_fsm import fused

    monkeypatch.setattr(fused_step, "default_interpret", lambda: False)
    cfg = MemSimConfig(channels=2, ranks=2, bankgroups=4, banks_per_group=4,
                       queue_size=128, fsm_backend="fused")
    assert (fused.NUM_TIMING_ROWS, fused.NUM_BANK_ROWS_IN,
            fused.NUM_BANK_ROWS_OUT) == (7, 23, 22)
    fn, args = _engine(cfg, lanes)
    args = jax.tree_util.tree_map(lambda x: _sds(one_chip, np.shape(x)),
                                  args)
    lead = () if lanes is None else (lanes,)
    carry = _while_carry(jax.make_jaxpr(jax.jit(fn))(*args))
    assert carry.count(lead + (cfg.num_ranks, cfg.bankgroups)) == 1, carry
    operands, results = _kernel_shapes(jax.jit(fn).lower(*args).compile()
                                       .as_text())
    lanes = lanes or 1
    b = cfg.num_banks
    assert operands[0] == (23, lanes, b)
    assert results[0] == (22, lanes, b)
    assert results[2] == (lanes, 9 + 2 * cfg.channels)


def test_jedec_batched_engine_has_no_queue_wide_gather(one_chip,
                                                       monkeypatch):
    """The lane-batched fused engine at the DDR5-4800 sweep's shape (64
    lanes, 128 banks, queues of 128) under ``"jedec"``: Mosaic takes the
    kernel with its bank-group and precharge-gate rows (28 in, 27 out),
    the loop carries the three [R, BG] registers beside the tFAW window,
    and no gather reads every slot of every bank queue of every lane."""
    from repro.core import fused_step

    monkeypatch.setattr(fused_step, "default_interpret", lambda: False)
    cfg = MemSimConfig(channels=2, ranks=2, bankgroups=8, banks_per_group=4,
                       queue_size=128, fsm_backend="fused",
                       timing_model="jedec", tRRDS=8, tRRDL=12, tFAW=32,
                       tCCDS=8, tCCDL=12, tWTRS=52, tWTR=70)
    lanes = 64
    fn, args = _engine(cfg, lanes)
    args = jax.tree_util.tree_map(lambda x: _sds(one_chip, np.shape(x)),
                                  args)
    carry = _while_carry(jax.make_jaxpr(jax.jit(fn))(*args))
    assert carry.count((lanes, cfg.num_ranks, cfg.bankgroups)) == 3, carry
    text = jax.jit(fn).lower(*args).compile().as_text()
    operands, results = _kernel_shapes(text)
    b = cfg.num_banks
    assert operands[0] == (28, lanes, b)
    assert results[0] == (27, lanes, b)
    assert results[2] == (lanes, 9 + 2 * cfg.channels + 3)
    sizes = _gather_sizes(text)
    assert sizes, "no gather found: the HLO text pattern no longer matches"
    assert lanes * b * cfg.queue_size not in sizes, sizes
