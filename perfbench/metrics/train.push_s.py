"""Exchange, push side, s/round: the program's ``client.push_compute``
spans (``full_propagate``, int8 encode, ``plan_push``), summed over
clients."""

from perfbench.yardstick.spans import in_rounds


def read(ctx):
    return in_rounds(ctx, ("client.push_compute",))
