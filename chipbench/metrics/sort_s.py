"""Device seconds per product in XLA sort operations: ESC's lexsort, the
row binning's argsort and the hash epilogue's row argsort together."""

from chipbench import tracing


def read(run):
    if not run.products:
        return None
    s = run.summary.seconds(tracing.is_sort)
    return s / run.products if s > 0 else None
