"""model step: the whole step's share of the chip's bf16 peak.  The
operations every token processed in the traced window requires
(``flops.token_flops``: kept projection weights, LM head, attention over
the token's context; prompt and output tokens alike) over the window's
host-clock seconds times the peak."""


def read(ctx):
    if not ctx.get("flops") or not ctx.get("window_s"):
        return None
    return 100.0 * ctx["flops"] / (ctx["window_s"]
                                   * ctx["peak"]["bf16_flops"])
