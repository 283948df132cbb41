"""device_idle.council: 1 − (union of device op intervals / traced window),
in percent."""
from bench import readers


def read(run):
    return readers.device_idle_pct(run)
