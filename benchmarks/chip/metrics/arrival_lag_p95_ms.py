"""How late the open-loop client sent: submit time minus scheduled time,
95th percentile over the requests due in the window (host clock)."""

import numpy as np


def read(ctx):
    if ctx["cell"].mix["loop"] != "open":
        return None
    lag = [s.sent - s.sched for s in ctx["sent"] if s.sched < ctx["seconds"]]
    return 1e3 * float(np.percentile(lag, 95)) if lag else None
