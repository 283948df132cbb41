"""merges_per_dispatch.council: sides merged back in the window per batched
merge program the engine ran (engine counters ``merges`` and
``merge_dispatches``; a program without the second, or a window with no
merge, reads nothing)."""
from bench import readers


def read(run):
    if "merge_dispatches" not in run.stats_open:
        return None
    dispatches = readers.stat_delta(run, "merge_dispatches")
    return readers.stat_delta(run, "merges") / dispatches if dispatches else None
