"""Local training, s/round: the program's ``client.train_epoch`` spans
(blocks to the device and the jitted step, ending in
``block_until_ready``), summed over clients and epochs."""

from perfbench.yardstick.spans import in_rounds


def read(ctx):
    return in_rounds(ctx, ("client.train_epoch",))
