"""decrypt_cts.train: ciphertexts the guest decrypts per round
(``Stats.n_decrypt``), read before and after each round of the window and
averaged over the rounds.  Uncompressed MO candidates are decrypted one
ciphertext per slot group: n_fh x (n_b - 1) x split nodes x n_k."""


def read(run):
    vals = [u["decrypt_cts"] for u in run.units if "decrypt_cts" in u]
    if not vals:
        return None
    return sum(vals) / len(vals)
