"""95th percentile over the requests completed in the traced window of (finish
- first token) / (tokens - 1)."""
from lib import readers as R


def read(ctx):
    return R.p95_ms(ctx["record"]["tpot_s"])
