"""Host seconds the training loop spends per save, from the end of the
save step to the next step's batch request (or the end of ``main``): the
D2H copy, ``CheckpointManager.save``, and its ``wait`` on the previous
write."""


def read(rec):
    d = rec.spans.done.get("bench.save_stall")
    return sum(d) / len(d) if d else None
