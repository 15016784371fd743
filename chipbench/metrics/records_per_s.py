"""Real records the harness sent into the leaf level, over every job of the
window, per second from the window's start to the end of its last job.
Counted by the harness, never by the program (``correct`` holds the leaf
counter to the same count)."""


def read(w):
    if not w.jobs or w.elapsed_s <= 0:
        return None
    return sum(j.records_sent for j in w.jobs) / w.elapsed_s
