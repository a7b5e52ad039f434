"""What the glue-scope readers share: each traced device op's named scope.

The program wraps its glue in ``jax.named_scope``: ``memsim.glue.pre``
(admission, the queue-head gather) and ``memsim.glue.post`` (the record
and memory scatters), which the compiler keeps as each instruction's
``op_name`` metadata. :func:`glue_scopes` maps every instruction of the
programs the engine compiled (``engine.compiled_texts``) to the innermost
``memsim.glue.*`` scope in its ``op_name``, keyed as
:mod:`bench.trace_reduce` keys the trace's ops (name, result shape,
opcode). A fusion carries its root instruction's metadata, so a fusion
counts to the scope of its root. An op of another program with the same
name, shape and opcode would count too; the engine's programs are the
only large ones a cell runs.

A program that cannot list its compiled programs maps nothing: the
readers then return None."""

from __future__ import annotations

import re
from typing import Dict, Optional

from bench.metrics._common import device_mean, kernel
from bench.trace_reduce import OPCODE, _op_key

OP_NAME = re.compile(r'op_name="([^"]*)"')
GLUE = re.compile(r"memsim\.glue\.([A-Za-z0-9_]+)")


def glue_scopes() -> Optional[Dict[str, str]]:
    """Op key -> innermost glue scope (``pre`` or ``post``), or None."""
    from repro.core import engine

    texts = getattr(engine, "compiled_texts", None)
    if texts is None:
        return None
    scopes = {}
    for text in texts():
        for line in text.splitlines():
            line = line.strip()
            if line.startswith("ROOT "):
                line = line[5:]
            m = OP_NAME.search(line)
            glue = GLUE.findall(m.group(1)) if m else None
            if glue:
                scopes[_op_key(line, OPCODE.search(line))] = glue[-1]
    return scopes


def us_per_step(ctx: Dict, scope: str) -> Optional[float]:
    """Device time per executed step of the ops in glue scope ``scope``,
    in microseconds (mean over the devices used; steps are the fused
    kernel's events, as :mod:`bench.metrics._common` counts them)."""
    if ctx["trace"] is None:
        return None
    scopes = glue_scopes()
    if not scopes:
        return None

    def one(d):
        k = kernel(d)
        if not k:
            return None
        s = sum(t for op, t in d["ops"].items() if scopes.get(op) == scope)
        return 1e6 * s / k[1]

    return device_mean(ctx, one)
