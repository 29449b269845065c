"""Share of the traced window in which no operation ran on the device:
1 less the busy union over the window, in per cent."""


def read(seen):
    return 100.0 * seen["trace"]["idle_share"]
