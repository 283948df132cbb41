"""window_device_ms.council: mean device time of one macro-window program
execution in the traced window (model step, fused_tick)."""
from bench import readers


def read(run):
    return readers.window_program_ms(run)
