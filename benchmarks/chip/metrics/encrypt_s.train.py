"""encrypt_s.train: seconds per round the guest spends encrypting g/h
(``Stats.encrypt_seconds``: host encoding, the transfer and the modmul
kernels, ended by ``block_until_ready``), read before and after each
round of the window and averaged over the rounds."""


def read(run):
    vals = [u["encrypt_s"] for u in run.units if "encrypt_s" in u]
    if not vals:
        return None
    return sum(vals) / len(vals)
