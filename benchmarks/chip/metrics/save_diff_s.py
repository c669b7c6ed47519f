"""Host seconds of a save's change detection on the writer thread (the
program's ``ckpt.diff`` span: the previous layers read, every chunk
serialized and hashed, the changed chunks copied), mean over the
window's saves."""
from chipbench.program_spans import mean_seconds


def read(rec):
    return mean_seconds(rec, "ckpt.diff")
