"""95th percentile of how late the load generator sent its ops against
their schedule: a starved generator is not a fast server."""
import numpy as np


def read(ctx):
    if len(ctx.lag_s) == 0:
        return None
    return float(np.percentile(ctx.lag_s, 95) * 1e3)
