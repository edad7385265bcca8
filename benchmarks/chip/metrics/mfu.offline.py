"""Dense-equivalent operations of the samples completed in the traced
window, over the window, over the cell's chips times the bf16 peak, in %:
the whole call's share of the peak (no Pallas kernel is on the path)."""


def read(rec):
    samples = rec["counters"].get("samples")
    if not samples:
        return None
    return (100.0 * samples * rec["flops_per_sample"] / rec["window_s"]
            / (rec["chips"] * rec["peak_flops"]))
