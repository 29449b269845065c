"""Tokens generated for each decode step over the window, from the
server's own counters before and after it: how many of the engine's slots
a decode step fills."""


def read(seen):
    counts = seen["counters"]
    if not counts.get("decode_steps"):
        return None
    return counts["tokens_generated"] / counts["decode_steps"]
