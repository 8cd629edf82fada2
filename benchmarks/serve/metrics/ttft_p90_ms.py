"""90th percentile, over every request due in the window, of first-token
time minus due time; a request that failed or never got a first token
counts as +inf."""
from runlib import percentile, ttfts_due_in_window


def read(run):
    v = ttfts_due_in_window(run)
    return percentile(v, 90) * 1e3 if v else None
