"""idle_share.serve: share of the traced window of served batches in
which no operation ran on the device, in percent (``trace_reduce``)."""


def read(run):
    if run.trace_summary is None:
        return None
    return 100.0 * run.trace_summary["idle_share"]
