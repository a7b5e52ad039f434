"""The plain reference of the ``"jedec"`` timing model (bank-group windows
and a per-bank precharge gate): a frozen copy of the per-cycle circuit,
importing nothing of the program, and an independent protocol checker of
the commands it issues (:mod:`bench.reference_jedec.protocol`)."""
