"""Share of the traced window in which no operation ran on the device, in
%, averaged over the cell's chips."""


def read(rec):
    return 100.0 * (1.0 - rec["busy_mean_s"] / rec["window_s"])
