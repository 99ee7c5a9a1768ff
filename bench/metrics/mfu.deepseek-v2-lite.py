"""mfu.deepseek-v2-lite: model FLOPs of the DeepSeek-style LM
meta-training rounds in the traced window over the window, the chips and
the chip's bf16 peak, in %.

The FLOPs are the configuration's own count from shapes (its reference
module: 6 per matmul weight per token, the routed experts at the
expected pairs a token sends to the held ones, causal attention, forward
and backward of each client's support and query pass; recompute not
counted)."""


def read(summary, work, peaks):
    if work.get("driver") != "lm_train_moe" or not work.get("rounds"):
        return None
    flops = work["flops_per_round"] * work["rounds"]
    return 100.0 * flops / (summary.window_s * summary.chips
                            * peaks["bf16_flops_per_s"])
