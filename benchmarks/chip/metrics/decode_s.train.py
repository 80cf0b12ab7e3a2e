"""decode_s.train: seconds per round in the ``decode`` span, on the host:
decrypted limbs to Python ints (``limbs.to_pyints``), the compressed
packages unpacked (``decompress_ints``) and the g/h sums decoded
(``codec.decode``).  Read from the traced window (``program_spans``)."""

import program_spans


def read(run):
    return program_spans.seconds_per_unit(run, "round", "decode")
