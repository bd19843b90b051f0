"""What the query files share: Arrow widths for ``min_bytes`` and the few
numpy helpers of the plain references. Nothing here imports the program."""
from __future__ import annotations

from datetime import date, timedelta

import numpy as np

EPOCH = date(1970, 1, 1)

#: bytes a column holds per row in Arrow's layout. Strings are a 4-byte
#: offset plus the mean length of the generator's values for that column.
ARROW_WIDTH = {
    "int64": 8, "double": 8, "int32": 4, "date32": 4,
    "l_returnflag": 4 + 1, "l_linestatus": 4 + 1, "c_mktsegment": 4 + 9,
}


def days(d: str) -> int:
    """ISO date → days since 1970-01-01 (Arrow's date32)."""
    return (date.fromisoformat(d) - EPOCH).days


def as_date(n) -> date:
    return EPOCH + timedelta(days=int(n))


def column_bytes(rows: dict, columns: dict) -> int:
    """Every row of each referenced column, once, at its Arrow width.
    ``columns`` is ``{table: {column: width key}}``."""
    return sum(
        rows[table] * ARROW_WIDTH[kind]
        for table, cols in columns.items()
        for kind in cols.values()
    )


