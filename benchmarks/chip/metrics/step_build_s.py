"""Host seconds to build the window's train step: the program's
``train.build`` span (``make_train_step``) plus what its compile counter
saw over the step's first call (tracing, lowering, and the backend
compile or the load from the persistent compile cache)."""
from chipbench.program_spans import in_window


def read(rec):
    builds = in_window(rec, "train.build")
    if not builds:
        return None
    b = builds[-1]
    return b.seconds + sum(b.counts.get(k, 0.0)
                           for k in ("trace_s", "lower_s", "compile_s"))
