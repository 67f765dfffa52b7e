"""Device milliseconds of one engine round, from the program's own
span: the device's busy time inside ``serve.step``, which covers the
whole of ``ServeEngine.step()`` and, since the step waits for its
results, the device work it dispatched, averaged over the rounds of the
window.  The in-program twin of ``round_device_ms.gen``."""
import readers

LABEL = "serve.step"


def read(run):
    return readers.busy_ms(run, LABEL)
