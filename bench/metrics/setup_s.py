"""setup_s: seconds from the start of the process to the opening of the
window — loading, weights, warm-up compiles, and the pre-roll of traffic."""


def read(run):
    return run.setup_s
