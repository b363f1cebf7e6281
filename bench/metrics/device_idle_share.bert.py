"""1 - union of device-op intervals over the traced window."""
from lib import readers as R


def read(ctx):
    return R.idle_pct(ctx)
