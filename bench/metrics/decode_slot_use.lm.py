"""Tokens emitted over k x slots attempted, summed over the engine's
``repro.engine.window`` spans: the share of a fused window's row-steps that
did useful work."""
from lib import spans as SP


def read(ctx):
    return SP.slot_use_pct(SP.program_spans(SP.WINDOW))
