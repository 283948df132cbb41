"""step_mfu.council: model FLOPs of the tokens delivered in the window
(dense matmuls, tied head, attention over the slots held) per second, over
the chip's bf16 peak, in percent."""
from bench import readers


def read(run):
    return readers.step_mfu(run)
