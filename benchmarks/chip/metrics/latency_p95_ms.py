"""95th percentile of request latency, open() to the completing step(),
over every request completed in the window, in ms."""

import numpy as np


def read(ctx):
    lat = [x for u in ctx.units for x in u.get("latencies_s", [])]
    return 1e3 * float(np.percentile(lat, 95)) if lat else None
