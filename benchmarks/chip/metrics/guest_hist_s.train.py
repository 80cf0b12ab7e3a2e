"""guest_hist_s.train: seconds per round in the guest's plaintext
histograms (``Stats.guest_hist_seconds``), read before and after each
round of the window and averaged over the rounds."""


def read(run):
    vals = [u["guest_hist_s"] for u in run.units if "guest_hist_s" in u]
    if not vals:
        return None
    return sum(vals) / len(vals)
