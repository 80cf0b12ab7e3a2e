"""host_wait_s.train: seconds per round the guest waits on the host's
cipher histograms, the decryption and the decoding
(``Stats.host_wait_seconds``), read before and after each round of the
window and averaged over the rounds."""


def read(run):
    vals = [u["host_wait_s"] for u in run.units if "host_wait_s" in u]
    if not vals:
        return None
    return sum(vals) / len(vals)
