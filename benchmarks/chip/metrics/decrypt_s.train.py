"""decrypt_s.train: seconds per round in the ``decrypt`` span, from the
start of the guest's wait for a host's ``split_infos`` until the decrypted
plaintext limbs are numpy arrays: the host's device pipeline still in
flight and the ``decrypt_batch`` kernels.  Read from the traced window
(``program_spans``)."""

import program_spans


def read(run):
    return program_spans.seconds_per_unit(run, "round", "decrypt")
