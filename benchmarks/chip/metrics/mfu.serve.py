"""mfu.serve: the least time the chip could take for one batch's required
work (``costs.serve_batch``, from the configuration's and traffic's
shapes) over the measured time per batch, in percent."""

import costs


def read(run):
    if not run.units or "rows" not in run.units[0]:
        return None
    d, p, t = run.config["data"], run.config["params"], run.traffic
    work = costs.serve_batch(t["batch_rows"], d["guest_features"],
                             d["host_features"], t["model"]["trees"],
                             p["max_depth"], p["n_bins"])
    least = costs.least_seconds(work, costs.peaks(run.device_kind))
    return 100.0 * least / (run.window_s / len(run.units))
