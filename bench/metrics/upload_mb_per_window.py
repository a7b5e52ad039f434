"""Serving host loop: bytes the arrival-buffer upload sends to the device
per closed-loop window, in 10^6 bytes (program counter ``upload_bytes``
of the ``memsim.session.upload`` spans). A window that uploads sends the
whole buffer, 16 B x lanes x capacity; one that reuses the cached device
copy sends 0."""

from bench.metrics._spans import calls, windows


def read(ctx):
    found = calls(ctx)
    n = windows(found) if found else 0
    if not n:
        return None
    sent = sum(s.counters.get("upload_bytes", 0) for call in found
               for s in call)
    return 1e-6 * sent / n
