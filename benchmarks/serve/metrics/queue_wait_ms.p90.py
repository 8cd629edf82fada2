"""Admission: 90th percentile of (start of the step that admitted a request
minus its due time), over admitted requests due in the window."""
import math

from runlib import percentile


def read(run):
    v = [r.admit_step_t0 - r.due for r in run.reqs.values()
         if run.tl.w0 <= r.due < run.tl.w1 and not math.isnan(r.admit_step_t0)]
    return percentile(v, 90) * 1e3 if v else None
