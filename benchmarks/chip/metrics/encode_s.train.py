"""encode_s.train: seconds per round the guest spends encoding g/h into
plaintext limbs on the host, the ``encode`` span (``codec.encode_plain``):
the host's part of ``encrypt_s.train``, whose rest is the transfer and the
modmul kernels.  Read from the traced window (``program_spans``)."""

import program_spans


def read(run):
    return program_spans.seconds_per_unit(run, "round", "encode")
