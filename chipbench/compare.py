"""The comparison that decides ``correct``: one job's grouped result
against the plain reference of the stream that was sent, and the leaf
level's counter against the records sent.

Every number is a count or an exact difference, and every limit is 0:
each total is a sum of integers far below 2**24, which float32 holds
exactly whatever the order of the additions.
"""

from __future__ import annotations

import numpy as np

#: each number compared, with its limit
LIMITS = {
    # keys the result gets wrong: missing, not in the reference, repeated,
    # or with a total that is not exactly the reference's
    "keys_wrong": 0,
    "total_err_max": 0.0,  # largest |total - reference| over all keys
    # |records the leaf level counted - real records the harness sent|:
    # the end-to-end metrics count records sent, the node's roofline the
    # program's counters, and both have to agree
    "leaf_gap": 0,
}


def compare(keys, values, ref_ids, ref_totals, n_in: int,
            records_sent: int) -> dict:
    """Numbers of one job's result (``keys``/``values`` as returned, -1
    where unused) against the reference's sorted keys and totals, and of
    its leaf counter ``n_in`` against ``records_sent``.  A record lost or
    counted twice changes its key's total, so the first two numbers see
    it; padding or a lost record counted as sent changes the third."""
    keys = np.asarray(keys)
    values = np.asarray(values, np.float64)
    real = keys != -1
    got_k, got_v = keys[real], values[real]
    uniq, inv, reps = np.unique(got_k, return_inverse=True,
                                return_counts=True)
    got_tot = np.bincount(inv, weights=got_v, minlength=uniq.shape[0])
    _, gi, ri = np.intersect1d(uniq, ref_ids, assume_unique=True,
                               return_indices=True)
    only_got = np.ones(uniq.shape[0], bool)
    only_got[gi] = False
    only_ref = np.ones(ref_ids.shape[0], bool)
    only_ref[ri] = False
    err = np.abs(got_tot[gi] - ref_totals[ri])
    errs = [err, np.abs(got_tot[only_got]), np.abs(ref_totals[only_ref])]
    wrong = (err != 0) | (reps[gi] > 1)
    return {
        "keys_wrong": int(wrong.sum() + only_got.sum() + only_ref.sum()),
        "total_err_max": float(max((e.max() for e in errs if e.size),
                                   default=0.0)),
        "leaf_gap": abs(int(n_in) - int(records_sent)),
    }


def compare_job(job, ref_ids, ref_totals) -> dict:
    """:func:`compare` of a :class:`~chipbench.jobs.JobResult`."""
    return compare(job.keys, job.values, ref_ids, ref_totals, job.n_in,
                   job.records_sent)


def passes(numbers: dict) -> bool:
    return all(numbers[k] <= lim for k, lim in LIMITS.items())


def worst(all_numbers: list[dict]) -> dict:
    """The largest reading of each number over the jobs compared."""
    return {k: max(n[k] for n in all_numbers) for k in LIMITS}
