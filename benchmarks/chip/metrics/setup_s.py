"""Set-up seconds: from the start of the process to the window,
loading, making the weights, compiling or loading every program the
window uses, and the traffic's own set-up."""


def read(run):
    return run.facts["setup_s"]
