"""Host seconds the window's restore spends reading the checkpoint's
blobs and assembling its tensors (the program's ``store.load`` spans
inside its ``ckpt.restore``), before the tensors are placed on the
device."""
from chipbench.program_spans import in_window


def read(rec):
    restores = in_window(rec, "ckpt.restore")
    if not restores:
        return None
    ids = {s.id for s in restores}
    loads = [s for s in in_window(rec, "store.load") if s.parent in ids]
    return sum(s.seconds for s in loads) if loads else None
