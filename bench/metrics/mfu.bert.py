"""Required FLOPs of the sequences served in the window over window x peak bf16
FLOP/s."""
from lib import readers as R


def read(ctx):
    return R.flops_pct(ctx)
