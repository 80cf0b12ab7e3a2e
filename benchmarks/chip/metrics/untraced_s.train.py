"""untraced_s.train: seconds per round that no program span covers: the
``round`` record's time less the union of the spans inside it on its
thread (its self time).  What the program's spans cannot yet name.  Read
from the traced window (``program_spans``)."""

import program_spans


def read(run):
    return program_spans.self_seconds_per_unit(run, "round")
