"""device_ms_per_batch.serve: device busy time per served batch in the
traced window (the union of device operation intervals, ``trace_reduce``,
over the number of batches), in milliseconds.  The device's part of a
batch: steadier than the host clock's time per batch, whose host part
between device calls varies from run to run."""


def read(run):
    if run.trace_summary is None or not run.units:
        return None
    return 1000.0 * run.trace_summary["busy_s"] / len(run.units)
