"""Local training, s/round: the program's ``client.step_inputs`` spans
(a minibatch's block arrays built from numpy and copied to the device),
summed over every step.  A part of ``train.epoch_s``."""

from perfbench.yardstick.recorded import span_in_rounds


def read(ctx):
    return span_in_rounds(ctx, "client.step_inputs")
