"""exec/ joins against the chip's memory bandwidth: the least time HBM could
take to stream the bytes the query's joins must touch (``join_min_bytes``
beside the query, over the peak of ``peaks.json``) as a share of the device
time of the ``jit__join_*`` modules in the traced part. The rows are the work
itself, whatever implements it: the pairs, the rows of the returns join and
the probe rows in and out of each semi join as the plain reference counted
them, which the run computes before it reads its metrics. Bound by bytes: a
join compares keys and copies rows."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import ds_q95  # noqa: E402  (queries/ is on the path)
from join_ms import join_seconds  # noqa: E402


def read(run):
    seconds = join_seconds(run)
    gbps = run.peaks.get("hbm_gbps")
    if not seconds or not gbps or not ds_q95.COUNTS:
        return None
    least_s = ds_q95.join_min_bytes(ds_q95.COUNTS) * len(run.traced_requests) / (gbps * 1e9)
    return 100.0 * least_s / seconds
