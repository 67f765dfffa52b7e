"""Paged attention's share of its roofline: compute or bytes for the
live contexts of every round, over the kernel's device time."""
import readers

KERNELS = (r"^%paged_attention_varlen\b",)


def read(run):
    return readers.roofline_pct(run, KERNELS)
