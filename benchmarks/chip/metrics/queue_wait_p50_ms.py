"""Median wait of a request in the engine's queue: ``submit()`` to the
start of its first ``engine.prefill`` span (engine counters, host
clock).  None where the program keeps no such samples."""

import numpy as np


def read(ctx):
    waits = getattr(ctx["stats"], "queue_wait_ns", None)
    return float(np.median(list(waits))) / 1e6 if waits else None
