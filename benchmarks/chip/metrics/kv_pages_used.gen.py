"""Mean share of the KV pool's pages held after each step."""
import readers


def read(run):
    return readers.pages_pct(run)
