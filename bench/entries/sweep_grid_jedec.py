"""``sweep_grid`` on a ``"jedec"`` configuration: a runtime-parameter
grid as lanes of one program, held to the bank-group reference.

The lanes run exactly as :class:`bench.entries.sweep_grid.SweepGrid`
runs them. What differs:

* the twin: the plain reference of a ``"jedec"`` configuration is
  :mod:`bench.reference_jedec`, whose parameters know the bank-group
  windows and the precharge gate that :mod:`bench.reference` cannot take.
  Each compared lane's reference also returns its issued-command log, and
  :func:`bench.reference_jedec.protocol.violations` checks every window
  over it: ``protocol_violations``, limit 0;
* what a job's seed changes. The generator's own use of it, reordering
  the KV gathers of each token, changes how long a dense burst drains, so
  the work would differ from seed to seed. Here the request set and its
  order are the draw's (``draw_seed``), and a job's seed relabels the rows
  instead: one seed-drawn mask XORed into every address's row bits. Rows
  stay equal where they were equal, and every bank, bank group, rank,
  channel and column stays as it was, so every job is the same work, while
  the addresses, and so the words read, are the job's own;
* the control, which leaves the write-recovery precharge gate unenforced
  in the program only (``tWTP`` of 1 cycle): a bank precharges as soon as
  its write's data wait ends.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

from bench import generators
from bench.entries import Job
from bench.entries.sweep_grid import SweepGrid, grid_points

#: the control's broken guarantee: the WR->PRE gate left unenforced
CONTROL = {"tWTP": 1}
#: generated addresses are under 2**ADDR_BITS; the row mask keeps them so
ADDR_BITS = 30


def ref_trace_of(arrays):
    """The jedec reference's Trace of generated arrays."""
    import jax.numpy as jnp

    from bench.reference_jedec.simulator import Trace

    return Trace(*(jnp.asarray(a) for a in arrays))


def relabel_rows(addr: np.ndarray, row_shift: int, seed: int) -> np.ndarray:
    """``addr`` with one mask, drawn from ``seed``, XORed into the row bits
    (those from ``row_shift`` up to :data:`ADDR_BITS`)."""
    mask = int(np.random.default_rng(seed).integers(
        0, 1 << (ADDR_BITS - row_shift)))
    return (addr ^ np.int32(mask << row_shift)).astype(np.int32)


class SweepGridJedec(SweepGrid):
    """A grid of lanes on ``repro.core.sweep_grid`` over a ``"jedec"``
    configuration, checked against :mod:`bench.reference_jedec`."""

    def __init__(self, config: Dict, traffic: Dict, *,
                 control: bool = False):
        # the base entries build their twin from bench.reference, which
        # has no jedec fields: build both configurations here instead
        from bench.reference_jedec import params as ref_params
        from repro.core import params as prog_params
        from repro.core import sweep_grid

        self.config, self.traffic = config, traffic
        timings = dict(config["timings"])
        self.control = CONTROL if control else {}
        self.cfg = prog_params.MemSimConfig(
            **config["topology"], **dict(timings, **self.control),
            fsm_backend=config["backend"])
        self.ref_cfg = ref_params.MemSimConfig(**config["topology"],
                                               **timings, fsm_backend="jnp")
        if config.get("cxl_tier"):
            raise ValueError("sweep_grid_jedec cells take one-tier "
                             "configurations")
        topo = self.ref_cfg.topology()
        self.row_shift = topo.addr_low_bits + topo.column_bits
        self.params = self.ref_params = None
        self.program = sweep_grid
        self.generate = generators.get(traffic["generator"])
        self.horizon = int(traffic["horizon"])
        self.grid = {k: list(v) for k, v in traffic["grid"].items()}
        self.points = grid_points(self.grid)
        self.run_grid = {k: [self.control.get(k, x) for x in v]
                         for k, v in self.grid.items()}

    def inputs(self, seed: int):
        params = self.traffic["params"]
        t, addr, is_write, wdata = self.generate(params, params["draw_seed"])
        return t, relabel_rows(addr, self.row_shift, seed), is_write, wdata

    def reference(self, job: Job, lane: int):
        """``(result, command log, the lane's stated timings)``."""
        from bench.reference_jedec.simulator import simulate

        point = dict(self.points[lane])
        q = point.pop("queue_size", self.ref_cfg.queue_size)
        cfg = dataclasses.replace(self.ref_cfg, **point)
        res, log = simulate(cfg, ref_trace_of(job.inputs), job.clock_cycles,
                            queue_size=q, log=True)
        return res, log, cfg

    def compare(self, res, ref) -> Dict[str, int]:
        from bench.reference_jedec import protocol

        ref, log, cfg = ref
        gaps = {w: getattr(cfg, w) for w in protocol.WINDOWS}
        broken = protocol.violations(log, self.config["topology"], gaps)
        return dict(super().compare(res, ref),
                    protocol_violations=sum(broken.values()))


ENTRY = SweepGridJedec
