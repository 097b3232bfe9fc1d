"""Model step: useful model FLOPs of the traced steps (fed tokens through
the layers, causal attention, the head for sampled rows only) over the
traced window times the chips' bf16 peak, in percent."""


def read(run):
    return run.mfu_pct()
