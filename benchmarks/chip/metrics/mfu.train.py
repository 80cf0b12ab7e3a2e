"""mfu.train: the least time the chip could take for one round's required
work (``costs.train_round``, from the configuration's shapes) over the
measured ``round_s``, in percent."""

import costs


def read(run):
    if not run.units or "encrypt_s" not in run.units[0]:
        return None
    d, p = run.config["data"], run.config["params"]
    work = costs.train_round(d["rows"], d["guest_features"],
                             d["host_features"], p["max_depth"],
                             p["n_bins"], p["key_bits"])
    least = costs.least_seconds(work, costs.peaks(run.device_kind))
    return 100.0 * least / (run.window_s / len(run.units))
