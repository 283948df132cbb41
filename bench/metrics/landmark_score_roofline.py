"""landmark_score_roofline: least time of the traced landmark_score sweeps
(spawn compression: layers × main-cache slots × d_head) over their summed
device time, in percent of the roofline."""
from bench import readers


def read(run):
    return readers.kernel_roofline(run, "landmark_score", readers.landmark_call)
