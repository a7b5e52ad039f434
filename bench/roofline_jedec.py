"""The work one executed step must do under the ``"jedec"`` timing model.

On top of what :func:`bench.roofline.step_bytes` counts for every
configuration, a ``"jedec"`` edge has to read and write each rank's
bank-group windows — the last ACT, RD and WR of every bank group, three
``[ranks, bankgroups]`` registers — and each bank's precharge gate. As
there, the count comes from the configuration and the lane count alone,
never from a kernel's operand shapes.
"""

from __future__ import annotations

from typing import Dict

from bench.roofline import WORD_BYTES, step_bytes

GROUP_TIMING_WORDS = 3         # last ACT, last RD, last WR per bank group
BANK_GATE_WORDS = 1            # the precharge gate per bank


def step_bytes_jedec(config: Dict, lanes: int) -> int:
    """Bytes one executed step must move for ``lanes`` lanes of a
    ``"jedec"`` ``config``: :func:`bench.roofline.step_bytes` plus the
    bank-group registers and the precharge gates, each word read once and
    written once."""
    topo = config["topology"]
    ranks = topo["channels"] * topo["ranks"]
    banks = ranks * topo["bankgroups"] * topo["banks_per_group"]
    words = (ranks * topo["bankgroups"] * GROUP_TIMING_WORDS
             + banks * BANK_GATE_WORDS)
    return step_bytes(config, lanes) + 2 * WORD_BYTES * words * lanes
