"""Exchange, pull side, s/round: the program's ``client.pull`` spans (the
int8 wire crossing of the pulled rows and the cache build), summed
over clients."""

from perfbench.yardstick.spans import in_rounds


def read(ctx):
    return in_rounds(ctx, ("client.pull",))
