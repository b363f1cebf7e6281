"""Summed duration of the program's ``repro.servable.forward`` spans over the
traced window: the share of it the host spent dispatching."""
from lib import spans as SP


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    return SP.window_share_pct(SP.program_spans(SP.FORWARD), tr["window_s"])
