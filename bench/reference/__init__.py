"""The plain reference the benchmark compares with: a frozen copy of the
per-cycle circuit and of the serving loop, importing nothing of the
program."""
