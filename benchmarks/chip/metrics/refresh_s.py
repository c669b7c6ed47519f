"""Host seconds of ``Engine.refresh`` until the swapped leaves are on the
device, mean over the window's cycles."""


def read(rec):
    d = rec.spans.done.get("bench.engine_refresh")
    n = rec.counters.get("cycles", 0)
    return sum(d[-n:]) / n if d and n else None
