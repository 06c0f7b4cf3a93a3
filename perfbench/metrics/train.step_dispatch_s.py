"""Local training, s/round: the program's ``client.step_dispatch`` spans
(host time to dispatch the jitted step, back-pressure waits included),
summed over every step.  A part of ``train.epoch_s``."""

from perfbench.yardstick.recorded import span_in_rounds


def read(ctx):
    return span_in_rounds(ctx, "client.step_dispatch")
