"""Host seconds of a save's commit (the program's ``store.commit`` span:
the deferred fsyncs of the save's blobs and layers, then the config and
the manifest), mean over the window's saves."""
from chipbench.program_spans import mean_seconds


def read(rec):
    return mean_seconds(rec, "store.commit")
