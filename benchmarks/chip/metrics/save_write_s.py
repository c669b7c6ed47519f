"""The store's own time to write one incremental save
(``BuildReport.wall_seconds``), mean over the window's saves."""


def read(rec):
    d = rec.counters.get("save_write_s")
    return sum(d) / len(d) if d else None
