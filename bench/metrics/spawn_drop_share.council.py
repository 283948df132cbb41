"""spawn_drop_share.council: [TASK] triggers the engine refused for want of
a free side lane, in percent of the triggers it saw in the window (engine
counters ``spawns`` and ``spawns_dropped``; a program without them reads
nothing)."""
from bench import readers


def read(run):
    if "spawns_dropped" not in run.stats_open:
        return None
    dropped = readers.stat_delta(run, "spawns_dropped")
    seen = readers.stat_delta(run, "spawns") + dropped
    return 100.0 * dropped / seen if seen else None
