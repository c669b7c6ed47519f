"""Host seconds of the copy of the state to the host before each save
(the program's ``ckpt.d2h`` span in ``repro.launch.train.main``), mean
over the window's saves."""
from chipbench.program_spans import mean_seconds


def read(rec):
    return mean_seconds(rec, "ckpt.d2h")
