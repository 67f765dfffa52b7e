"""90th percentile of time to first token, from the moment each
request was due, over every request due in the window."""
import readers


def read(run):
    v = readers.nearest_rank(readers.ttft_s(run), 90)
    return None if v is None else 1000.0 * v
