"""A plain JEDEC protocol checker over an issued-command log.

The independent statement of what the ``"jedec"`` timing model promises:
whatever the circuit did, every ordered pair of commands it issued keeps
every timing window in force between them. The checker knows nothing of
the circuit's registers, bids or arbiters; it reads the log alone, one
row ``(cycle, channel, flat bank, command)`` per issued command, and the
configuration's timings.

Each rule is "a command of kind B issued after a command of kind A in the
same rank, bank group or bank comes at least ``gap`` cycles later":

* rank: tRRDS (ACT->ACT), tCCDS (RD->RD, WR->WR), tWTRS (WR->RD),
  tRTW (RD->WR), and tFAW (an ACT and the fourth ACT after it);
* bank group: tRRDL (ACT->ACT), tCCDL (RD->RD, WR->WR), tWTR (WR->RD);
* bank: tRAS (ACT->PRE), tRTP (RD->PRE), tWTP (WR->PRE), tRCDRD
  (ACT->RD), tRCDWR (ACT->WR), tRP (PRE->ACT, PRE->REF), tRFC (REF->any
  command), tXS (SREF exit->any command).

Banks are numbered as the program flattens them,
``((channel * ranks + rank) * bankgroups + bankgroup) * banks_per_group
+ bank``. Pairs are checked within each rank, all of them: O(n^2) in the
commands of one rank, which a benchmark job or a test keeps to hundreds.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

#: the log's command encoding (``repro.core.params.CMD_*``)
ACT, RD, WR, PRE, REF, SREF_ENTER, SREF_EXIT = 1, 2, 3, 4, 5, 6, 7
ANY = (ACT, RD, WR, PRE, REF, SREF_ENTER, SREF_EXIT)

#: (window, scope, first commands, second commands)
RULES: List[Tuple[str, str, Tuple[int, ...], Tuple[int, ...]]] = [
    ("tRRDS", "rank", (ACT,), (ACT,)),
    ("tCCDS", "rank", (RD,), (RD,)),
    ("tCCDS", "rank", (WR,), (WR,)),
    ("tWTRS", "rank", (WR,), (RD,)),
    ("tRTW", "rank", (RD,), (WR,)),
    ("tRRDL", "group", (ACT,), (ACT,)),
    ("tCCDL", "group", (RD,), (RD,)),
    ("tCCDL", "group", (WR,), (WR,)),
    ("tWTR", "group", (WR,), (RD,)),
    ("tRAS", "bank", (ACT,), (PRE,)),
    ("tRTP", "bank", (RD,), (PRE,)),
    ("tWTP", "bank", (WR,), (PRE,)),
    ("tRCDRD", "bank", (ACT,), (RD,)),
    ("tRCDWR", "bank", (ACT,), (WR,)),
    ("tRP", "bank", (PRE,), (ACT, REF)),
    ("tRFC", "bank", (REF,), ANY),
    ("tXS", "bank", (SREF_EXIT,), ANY),
]
WINDOWS = tuple(dict.fromkeys([r[0] for r in RULES] + ["tFAW"]))


def violations(log: np.ndarray, topology: Dict, timings: Dict
               ) -> Dict[str, int]:
    """Ordered command pairs that break each window: ``{window: count}``
    for every window of :data:`WINDOWS` (0 where it holds).

    ``log`` is ``int[K, 4]`` rows ``(cycle, channel, flat bank,
    command)``; ``topology`` gives ``ranks``, ``bankgroups`` and
    ``banks_per_group``; ``timings`` every window's gap in cycles."""
    log = np.asarray(log, np.int64).reshape(-1, 4)
    per_group = int(topology["banks_per_group"])
    per_rank = int(topology["bankgroups"]) * per_group
    out = {w: 0 for w in WINDOWS}
    rank_of = log[:, 2] // per_rank
    for rank in np.unique(rank_of):
        rows = log[rank_of == rank]
        rows = rows[np.argsort(rows[:, 0], kind="stable")]
        t, bank, cmd = rows[:, 0], rows[:, 2], rows[:, 3]
        group = bank // per_group
        later = np.arange(len(t))[None, :] > np.arange(len(t))[:, None]
        dt = t[None, :] - t[:, None]                   # [first, second]
        scopes = {"rank": later,
                  "group": later & (group[:, None] == group[None, :]),
                  "bank": later & (bank[:, None] == bank[None, :])}
        for window, scope, first, second in RULES:
            pair = (scopes[scope] & np.isin(cmd, first)[:, None]
                    & np.isin(cmd, second)[None, :])
            out[window] += int((pair & (dt < int(timings[window]))).sum())
        acts = t[cmd == ACT]
        out["tFAW"] += int((acts[4:] - acts[:-4] < int(timings["tFAW"]))
                           .sum())
    return out
