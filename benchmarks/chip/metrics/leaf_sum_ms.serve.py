"""leaf_sum_ms.serve: milliseconds per served batch in the
``serve_leaf_sum`` span: the host's gather of every row's leaf weights and
their sum over the trees, after the routing loop.  Read from the traced
window (``program_spans``)."""

import program_spans


def read(run):
    s = program_spans.seconds_per_unit(run, "serve_batch", "serve_leaf_sum")
    return None if s is None else 1000.0 * s
