"""The whole step's share of the chip's bf16 peak: model operations of
every row computed in the window over the window."""
import readers


def read(run):
    return readers.mfu_pct(run)
