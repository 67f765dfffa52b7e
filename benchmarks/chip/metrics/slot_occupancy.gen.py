"""Mean share of the engine's slots held after each step."""
import readers


def read(run):
    return readers.occupancy_pct(run)
