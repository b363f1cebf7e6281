"""KV bytes of the active positions (not the cache padding) over HBM bandwidth,
over the device time of the flash-decode kernel events."""
from lib import readers as R


def read(ctx):
    return R.roofline_pct(ctx, "decode_attn")
