"""Share of decode slot-steps in the window that held a live request:
busy / (busy + bubble) from the engine's counters."""


def read(ctx):
    busy = ctx.delta("busy_slot_steps")
    bubble = ctx.delta("bubble_slot_steps")
    return busy / (busy + bubble) if busy + bubble > 0 else None
