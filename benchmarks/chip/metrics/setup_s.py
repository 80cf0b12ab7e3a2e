"""setup_s: process start to window start (data, binning, keys, warm-up,
compilation), on the host clock."""


def read(run):
    return run.setup_s
