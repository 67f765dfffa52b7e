"""Device milliseconds of one engine round: the device's busy time
inside the harness's ``bench.step`` annotation, which wraps one
``engine.step()`` and, since the step waits for its results, the device
work it dispatched, averaged over the rounds of the window."""
import readers

LABEL = "bench.step"


def read(run):
    return readers.busy_ms(run, LABEL)
