"""90th percentile of the scheduler's queue wait (submit or
re-queue to admission), from the program's own histogram."""
import readers


def read(run):
    return readers.histogram_pct_ms(run, "serve_queue_wait_s", 90)
