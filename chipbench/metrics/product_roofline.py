"""Roofline share of the whole product: its least time (row-wise bytes
over HBM bandwidth, ``work.py``) over the device-busy time per product."""


def read(run):
    if not run.products or run.summary.busy_s <= 0:
        return None
    return 100.0 * run.least_s / (run.summary.busy_s / run.products)
