"""95th percentile of the host-clock latency of every request in the window."""
from lib import readers as R


def read(ctx):
    return R.p95_ms(ctx["record"]["latencies_s"])
