"""host_layer_s.train: seconds per round in the host's layer work, the
``host_layer`` span of ``HostRuntime.on_assign_sync`` (histogram kernels
enqueued, ciphertext cumsum, shuffle, compression, the ``split_infos``
reply).  In-process it runs inside the guest's layer, before the guest's
histograms.  Read from the traced window (``program_spans``)."""

import program_spans


def read(run):
    return program_spans.seconds_per_unit(run, "round", "host_layer")
