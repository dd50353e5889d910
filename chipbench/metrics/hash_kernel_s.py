"""Device seconds per product in the Pallas hash kernels (the trace's
custom-call operations)."""

from chipbench import tracing


def read(run):
    if not run.products:
        return None
    s = run.summary.seconds(tracing.is_pallas)
    return s / run.products if s > 0 else None
