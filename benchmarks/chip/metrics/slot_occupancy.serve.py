"""Busy slot-chunks over all slot-chunks the engine stepped in the traced
window (``CSNNEngine.stats``), in %."""


def read(rec):
    c = rec["counters"]
    if not c.get("slot_steps_total"):
        return None
    return 100.0 * c["slot_steps_busy"] / c["slot_steps_total"]
