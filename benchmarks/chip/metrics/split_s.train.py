"""split_s.train: seconds per round in the ``split`` span, the per-layer
loop after the candidates: ``find_best_split``, the ``chosen_sid`` /
``assign_mask`` exchange with the host, the row partition and the cache
eviction at the layer's end.  Read from the traced window
(``program_spans``)."""

import program_spans


def read(run):
    return program_spans.seconds_per_unit(run, "round", "split")
