"""The work one executed step must do, and the peaks it is held against.

One executed step of the event-horizon engine advances every lane's
memory controller by one clock edge. Whatever the implementation, that
edge has to read and write each bank's scheduler state, the head of each
bank's queue and the rank timing windows, and the response queue. The
count below comes from the configuration and the lane count alone, never
from a kernel's operand shapes, so that every implementation is held to
the same work.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

WORD_BYTES = 4                 # every register is an int32
BANK_STATE_WORDS = 10          # st, timer, idle counter, refresh deadline,
                               # the in-flight request (addr, write, data,
                               # id), open row, pending action
QUEUE_HEAD_WORDS = 2 + 4       # head, count; the head request's 4 fields
RANK_TIMING_WORDS = 7          # last ACT, four ACT times (tFAW), last RD,
                               # last WR
RESP_ENTRY_WORDS = 4           # addr, write, data, id
RESP_QUEUE_META_WORDS = 2      # head, count

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def step_bytes(config: Dict, lanes: int) -> int:
    """Bytes one executed step must move for ``lanes`` lanes of
    ``config``: each word of state read once and written once."""
    topo = config["topology"]
    banks_per_channel = (topo["ranks"] * topo["bankgroups"]
                         * topo["banks_per_group"])
    banks = topo["channels"] * banks_per_channel
    ranks = topo["channels"] * topo["ranks"]
    words = (banks * (BANK_STATE_WORDS + QUEUE_HEAD_WORDS)
             + ranks * RANK_TIMING_WORDS
             + topo["resp_queue_size"] * RESP_ENTRY_WORDS
             + RESP_QUEUE_META_WORDS)
    return 2 * WORD_BYTES * words * lanes


def peaks(device_kind: str) -> Dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise ValueError(f"no peaks for device kind {device_kind!r} in "
                         f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]
