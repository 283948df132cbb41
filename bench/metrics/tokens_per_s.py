"""tokens_per_s: every token sampled on a river or side lane and delivered
to the host inside the window, over the window's seconds."""
from bench import readers


def read(run):
    return readers.tokens_per_s(run)
