"""Mean of the inter-token gaps ranked from the 90th to the 99th
percentile, in milliseconds.  Beside ``itl_p95_ms``, whose rank can fall
on either side of a step between the gaps of plain decode ticks and of
ticks that carry a prefill chunk: this mean moves smoothly with both."""

import numpy as np


def read(ctx):
    gaps = np.sort(np.asarray(ctx.counters.get("gaps") or [], np.float64))
    lo, hi = int(0.90 * len(gaps)), int(np.ceil(0.99 * len(gaps)))
    if hi <= lo:
        return None
    return 1e3 * float(gaps[lo:hi].mean())
