"""Device seconds of one train step: the mean duration of the compiled
train step's events (XLA module ``jit_step``) in the trace."""


def read(rec):
    if rec.trace is None:
        return None
    d = rec.trace.module_events(lambda name: name.startswith("jit_step"))
    return sum(d) / len(d) if d else None
