"""Share of slot-steps that held a request over the window's decode
steps (engine counters)."""


def read(rec):
    c = rec.counters
    if not c["slot_capacity"]:
        return None
    return 100.0 * c["active_slot_steps"] / c["slot_capacity"]
