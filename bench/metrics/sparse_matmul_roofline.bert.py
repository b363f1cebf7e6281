"""Least time of the packed projections' required work over the device time of
the plan_dds kernel events."""
from lib import readers as R


def read(ctx):
    return R.roofline_pct(ctx, "sparse_matmul")
