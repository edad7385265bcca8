"""Device busy time per offline call in the traced window, in ms; on
several chips, the busiest chip's."""


def read(rec):
    calls = rec["counters"].get("calls")
    if not calls:
        return None
    return 1e3 * max(rec["busy_s"].values()) / calls
