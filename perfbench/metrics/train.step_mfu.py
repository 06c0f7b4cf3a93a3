"""Model FLOP utilization of the local training steps, %: the model
FLOPs of every step in the traced rounds (the model module's
``step_flops``, ``yardstick/models/<conv>.py``, from the configuration's
batch, fanout, layers and widths) over the device time of the
train-step program (``jit__step``) in the trace, times the chip's bf16
peak.  The bf16 peak is the only matrix peak published for the chip; a
float32 product at ``highest`` precision runs as six bf16 passes, so at
that precision the step cannot read above about 17%."""


def read(ctx):
    dev = ctx["device"]
    if dev is None:
        return None
    step_s = sum(v for name, v in dev["modules"].items()
                 if name.split("(")[0] == "jit__step")
    if step_s <= 0:
        return None
    model_flops = ctx["work"]["flops_per_round"] * len(ctx["rounds"])
    return 100.0 * model_flops / step_s / ctx["peaks"]["bf16_flops_per_s"]
