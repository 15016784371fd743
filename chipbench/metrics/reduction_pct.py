"""The paper's data reduction ratio over the window's jobs:
100 x (1 - records leaving the last placed level / records sent into the
leaf); the first from the cascade's counter, the second counted by the
harness."""


def read(w):
    sent = sum(j.records_sent for j in w.jobs)
    if not sent:
        return None
    return 100.0 * (1.0 - sum(j.n_out for j in w.jobs) / sent)
