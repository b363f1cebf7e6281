"""Whole-step roofline share: the sum over decode steps and prefills of
max(FLOPs / peak, bytes / HBM bandwidth), over the window."""
from lib import readers as R


def read(ctx):
    return R.step_roofline_pct(ctx)
