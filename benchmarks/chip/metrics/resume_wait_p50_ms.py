"""Median wait of a preempted request: the end of its ``_preempt()`` to
its successful ``_resume()`` (engine counters, host clock).  None where
the program keeps no such samples."""

import numpy as np


def read(ctx):
    waits = getattr(ctx["stats"], "resume_wait_ns", None)
    return float(np.median(list(waits))) / 1e6 if waits else None
