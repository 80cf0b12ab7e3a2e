"""wire_mb.train: megabytes (1e6 B) per round that cross a party boundary,
from the program's ``Channel`` byte ledger before and after each round of
the window, averaged over the rounds."""


def read(run):
    vals = [u["wire_bytes"] for u in run.units if "wire_bytes" in u]
    if not vals:
        return None
    return sum(vals) / len(vals) / 1e6
