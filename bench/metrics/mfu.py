"""Model FLOPs of the rounds completed in the traced window, over the
window times the chips times the chip's peak (bench/flops, bench/peaks)."""


def read(ctx: dict):
    tr = ctx.get("trace")
    if not tr or not ctx.get("traced_flops"):
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * ctx["traced_flops"] / (tr["window_s"] * ctx["chips"]
                                          * peak)
