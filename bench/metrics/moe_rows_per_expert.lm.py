"""Rows the held experts computed per expert, layer and forward: the sum of
``moe_rows`` over the sum of ``moe_expert_slots``, which the engine sets on
its ``repro.engine.window`` and ``repro.engine.admit`` spans. None where no
span carries them (no MoE layer, or a program that does not count)."""
from lib import spans as SP


def read(ctx):
    spans = SP.program_spans(SP.WINDOW) + SP.program_spans(SP.ADMIT)
    slots = sum(sp.args.get("moe_expert_slots", 0) for sp in spans)
    if slots <= 0:
        return None
    return sum(sp.args.get("moe_rows", 0) for sp in spans) / slots
