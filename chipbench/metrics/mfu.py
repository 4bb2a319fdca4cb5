"""The whole step's share of the chips' bf16 peak: model FLOPs per token
(``arith.flops_per_token``) times tokens per second of the window, over
chips times peak."""
import arith


def read(ctx):
    if not ctx["window_s"]:
        return None
    flops = arith.flops_per_token(ctx["cfg"], ctx["traffic"]["seq_len"])
    rate = ctx["tokens"] / ctx["window_s"]
    return 100.0 * flops * rate / (ctx["chips"]
                                   * ctx["peaks"]["bf16_flops_per_s"])
