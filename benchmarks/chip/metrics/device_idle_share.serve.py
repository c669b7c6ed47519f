"""Share of the traced window in which no operation ran on the device."""


def read(rec):
    return None if rec.trace is None else 100.0 * rec.trace.idle_share
