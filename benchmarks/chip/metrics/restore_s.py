"""Host seconds of the window's ``reshard_restore``, until every
restored leaf is on the device."""


def read(rec):
    d = rec.spans.done.get("bench.restore")
    return d[-1] if d else None
