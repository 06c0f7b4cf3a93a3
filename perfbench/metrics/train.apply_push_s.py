"""Exchange, s/round: the program's ``round.apply_push`` span (every
client's planned push decoded and written to the embedding server).
A part of ``train.driver_self_s``."""

from perfbench.yardstick.recorded import span_in_rounds


def read(ctx):
    return span_in_rounds(ctx, "round.apply_push")
