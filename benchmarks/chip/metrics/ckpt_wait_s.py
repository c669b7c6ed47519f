"""Host seconds the training thread is blocked on a save's write (the
program's ``ckpt.wait`` spans: in the next save's call and at the end of
``main``), summed over the window and divided by its saves."""
from chipbench.program_spans import in_window


def read(rec):
    waits, saves = in_window(rec, "ckpt.wait"), in_window(rec, "ckpt.save")
    if waits is None or not saves:
        return None
    return sum(s.seconds for s in waits) / len(saves)
