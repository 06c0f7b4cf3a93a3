"""Aggregation server, s/round: the program's ``round.aggregate`` span
(FedAvg and ``evaluate``)."""

from perfbench.yardstick.spans import in_rounds


def read(ctx):
    return in_rounds(ctx, ("round.aggregate",))
