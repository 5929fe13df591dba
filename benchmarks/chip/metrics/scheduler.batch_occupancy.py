"""Scheduler and pool: share of decode slot-steps that decoded a token.

``active_slot_steps / (decode_steps * max_slots)`` over the window, from
the engine's own counters.
"""


def read(ctx):
    c0, c1 = ctx["counters"]
    steps = c1["decode_steps"] - c0["decode_steps"]
    if steps <= 0:
        return None
    active = c1["active_slot_steps"] - c0["active_slot_steps"]
    return 100.0 * active / (steps * ctx["config"]["engine"]["max_slots"])
