"""Least time of the held experts' required work (rows routed to them as the
shapes give them, every held expert's weights read once per forward and
layer) over the device time of the moe_gmm kernel events."""
from lib import readers as R


def read(ctx):
    return R.roofline_pct(ctx, "moe_gmm")
