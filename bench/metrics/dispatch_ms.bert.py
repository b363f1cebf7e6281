"""Median duration of the program's ``repro.servable.forward`` spans in the
traced window: the host's dispatch of one request, not its wait for the
logits."""
from lib import spans as SP


def read(ctx):
    return SP.median_ms(SP.program_spans(SP.FORWARD))
