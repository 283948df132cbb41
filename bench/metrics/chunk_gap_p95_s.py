"""chunk_gap_p95_s: 95th percentile of the gaps between successive token
deliveries to one request's stream, over all gaps of all requests in the
window — what a streaming reader waits between chunks."""
from bench import readers


def read(run):
    return readers.percentile(readers.chunk_gaps(run), 95)
