"""merge_device_ms.council: mean device time of one execution of the
engine's merge program in the traced window. The program is found by its
module name, ``jit_engine_merge``; a trace without it reads nothing."""

MERGE_PROGRAM = "jit_engine_merge"


def program_name(key: str) -> str:
    """A module event's program name, without the ``#<program id>`` the
    trace reduction appends or a ``(<id>)`` the profiler may add."""
    return key.split("#", 1)[0].split("(", 1)[0]


def read(run):
    tr = run.trace
    if tr is None:
        return None
    ev = tr.modules[0]
    w0, w1 = tr.window
    durs = [e - s for s, e, n in zip(ev.start, ev.end, ev.name)
            if s >= w0 and e <= w1 and program_name(n) == MERGE_PROGRAM]
    return 1e-6 * sum(durs) / len(durs) if durs else None
