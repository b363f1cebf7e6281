"""95th percentile of first token minus submit over every request submitted in
the traced window (one still waiting counts its wait so far, a failed one
counts as infinite)."""
from lib import readers as R


def read(ctx):
    return R.p95_ms(ctx["record"]["ttft_s"])
