"""resident.step_idle_ms: device idle inside the program's
``sz.resident.step`` spans (the dispatch of one jitted resident decode step
and its argmax), in ms per resident step of the traced batch. Moves
resident_tpot_ms."""

from bench import spans


def read(ctx):
    return spans.idle_ms_per(ctx.trace, "sz.resident.step",
                             "sz.resident.step")
