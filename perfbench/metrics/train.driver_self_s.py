"""Round driver and sampler, s/round: the harness-timed ``run_round``
minus the program spans inside it (pull, local epochs, push,
aggregate).  Most of it is minibatch sampling on the host."""

from perfbench.yardstick.spans import in_rounds, round_wall


def read(ctx):
    inner = in_rounds(ctx, ctx["round_spans"])
    wall = round_wall(ctx)
    if inner is None or wall is None:
        return None
    return wall - inner
