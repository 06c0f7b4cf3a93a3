"""Compiler, s/round: the program's ``jit.compile`` spans (each XLA
compile or compile-cache load) inside the traced rounds; 0 where
nothing compiled there.  A program that counts no compiles (no
``jit.compiles`` counter) records no such span, and reads nothing."""

from perfbench.yardstick.spans import in_rounds


def read(ctx):
    from repro.obsv.metrics import REGISTRY
    if "jit.compiles" not in REGISTRY.snapshot("jit."):
        return None
    return in_rounds(ctx, ("jit.compile",))
