"""exec/ joins: key-matched pairs the equi-joins sized their output batches
for, per completed query of the window, from the program's counter (the match
totals the host pulls to choose each output capacity; before any residual
condition). A program without the counter (the parent) reports nothing."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from subquery_host_values import counter_per_query  # noqa: E402


def read(run):
    return counter_per_query(run, "join.rowsOut")
