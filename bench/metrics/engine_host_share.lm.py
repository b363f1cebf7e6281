"""1 - (prefill_s + decode_s) / window: the engine loop's host time between
device calls (syncs, scheduling, page bookkeeping)."""
from lib import readers as R


def read(ctx):
    return R.host_share_pct(ctx["record"])
