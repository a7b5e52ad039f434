"""Engine layer (``core/engine.py``): executed steps per 1,000 simulated
cycles of the engine's clock. The event horizon skips every inert cycle,
so this is the share of cycles that cost a step."""


def read(ctx):
    from bench.metrics._common import steps

    cycles = sum(j.clock_cycles for j in ctx["jobs"])
    return 1000.0 * steps(ctx) / cycles if cycles else None
