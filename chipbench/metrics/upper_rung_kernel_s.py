"""Device seconds per product in the hash kernels of the upper rungs:
the trace's custom-call operations named for a table of 4,096 slots or
more (``opsparse_hash_t<size>``, rungs 4-7 of the symbolic ladder).
None where no kernel carries such a name."""

import re

from chipbench import tracing

UPPER_TABLE = 4096
_TABLE = re.compile(r"opsparse_hash_t(\d+)")


def table_size(op_name: str):
    """The table size an op's name carries, or None."""
    found = _TABLE.search(op_name)
    return int(found.group(1)) if found else None


def read(run):
    if not run.products:
        return None
    s = sum(sec for (name, kind), sec in run.summary.op_s.items()
            if tracing.is_pallas(kind)
            and (table_size(name) or 0) >= UPPER_TABLE)
    return s / run.products if s > 0 else None
