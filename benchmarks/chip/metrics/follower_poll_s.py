"""Host seconds of ``CheckpointFollower.poll`` (pull, verify, load), mean
over the window's cycles."""


def read(rec):
    d = rec.spans.done.get("bench.follower_poll")
    n = rec.counters.get("cycles", 0)
    return sum(d[-n:]) / n if d and n else None
