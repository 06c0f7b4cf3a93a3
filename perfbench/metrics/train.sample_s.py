"""Round driver and sampler, s/round: the program's ``client.sample``
spans (the NeighborSampler drawing a round's minibatches on the host),
summed over clients.  A part of ``train.driver_self_s``."""

from perfbench.yardstick.recorded import span_in_rounds


def read(ctx):
    return span_in_rounds(ctx, "client.sample")
