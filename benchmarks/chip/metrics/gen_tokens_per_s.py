"""Output tokens emitted in the window, finished or not, over the
window's seconds."""


def read(run):
    return run.tokens_out / run.window_s if run.window_s > 0 else None
