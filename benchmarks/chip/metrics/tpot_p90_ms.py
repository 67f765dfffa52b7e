"""90th percentile of time per output token after the first."""
import readers


def read(run):
    v = readers.nearest_rank(readers.tpot_s(run), 90)
    return None if v is None else 1000.0 * v
