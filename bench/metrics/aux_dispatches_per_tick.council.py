"""aux_dispatches_per_tick.council: the engine's spawn, merge, prefill and
admit dispatches in the window per virtual tick (engine stats delta)."""
from bench import readers


def read(run):
    ticks = readers.stat_delta(run, "ticks")
    return readers.stat_delta(run, "aux_dispatches") / ticks if ticks else None
