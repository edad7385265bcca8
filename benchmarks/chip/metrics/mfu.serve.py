"""Dense-equivalent operations of the requests the engine completed in the
traced window, over the device's busy seconds there, over the chip's bf16
peak, in %: the whole chunk step's share of the peak while the device
works (no Pallas kernel is on the served path)."""


def read(rec):
    done = rec["counters"].get("retired")
    if not done or rec["busy_mean_s"] <= 0:
        return None
    return (100.0 * done * rec["flops_per_sample"]
            / rec["busy_mean_s"] / rec["peak_flops"])
