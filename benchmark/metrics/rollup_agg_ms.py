"""ops/ kernels of the rollup's group-by: device milliseconds of the grouped
aggregate's jit module (``jit__aggregate``: key words, sort, segmented scan)
per traced query. The expand and the final sort beside it are under a
thousandth of it and can fall out of the ten modules ``trace_reduce`` keeps,
so they are not summed in."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from window_ms import module_seconds  # noqa: E402


def read(run):
    s = module_seconds(run, ("jit__aggregate",))
    return None if s is None else 1e3 * s / len(run.traced_requests)
