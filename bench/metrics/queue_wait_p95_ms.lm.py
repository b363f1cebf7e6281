"""95th percentile, over the requests admitted in the traced window, of the
engine's admission time minus submit (``wait_us``, which only the
``repro.engine.admit`` span that first makes a request active carries)."""
from lib import spans as SP


def read(ctx):
    return SP.queue_wait_p95_ms(SP.program_spans(SP.ADMIT))
