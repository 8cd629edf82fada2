"""95th percentile of every gap between consecutive output tokens of a
request, over all gaps that end in the window."""
from runlib import gaps_ending_in_window, percentile


def read(run):
    v = gaps_ending_in_window(run)
    return percentile(v, 95) * 1e3 if v else None
