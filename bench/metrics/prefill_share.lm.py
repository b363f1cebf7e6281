"""The engine's prefill time (compute and slot insert) over the window."""
from lib import readers as R


def read(ctx):
    return R.share_pct(ctx["record"]["prefill_s"], ctx["record"]["window_s"])
