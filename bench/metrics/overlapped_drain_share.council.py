"""overlapped_drain_share.council: share of the window's drains whose host
post-processing overlapped the next window on the device, in percent."""
from bench import readers


def read(run):
    drains = readers.stat_delta(run, "drains")
    return 100.0 * readers.stat_delta(run, "overlapped_drains") / drains if drains else None
