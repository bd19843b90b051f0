"""exec/ window against the chip's memory bandwidth: the least time HBM could
take to stream the bytes the window must touch (``window_min_bytes`` beside
the query, over the peak of ``peaks.json``) as a share of the device time of
``jit__window`` in the traced part. Rows in are the work itself: the groups
of each rollup level as the plain reference counted them, which the run
computes before it reads its metrics. Bound by bytes: a rank is index
arithmetic on a sorted batch."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import ds_q67  # noqa: E402  (queries/ is on the path)
from window_ms import MODULE, module_seconds  # noqa: E402


def read(run):
    seconds = module_seconds(run, (MODULE,))
    gbps = run.peaks.get("hbm_gbps")
    if not seconds or not gbps or not ds_q67.LEVEL_ROWS:
        return None
    least_s = ds_q67.window_min_bytes(ds_q67.LEVEL_ROWS) * len(run.traced_requests) / (gbps * 1e9)
    return 100.0 * least_s / seconds
