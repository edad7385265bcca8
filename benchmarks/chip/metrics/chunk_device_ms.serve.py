"""Device busy time per engine chunk in the traced window, in ms: the
union of the device's operation intervals over the chunks the engine ran
there (matched by time, not by operation names)."""


def read(rec):
    chunks = rec["counters"].get("chunks")
    if not chunks:
        return None
    return 1e3 * rec["busy_mean_s"] / chunks
