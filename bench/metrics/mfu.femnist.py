"""mfu.femnist: model FLOPs of the paper rounds in the traced window over
the window, the chips and the chip's bf16 peak, in %.

The FLOPs are the configuration's own count from shapes (its reference
module: the convolutions' and dense layers' multiply-adds, forward and
backward of each writer's support and query pass)."""


def read(summary, work, peaks):
    if work.get("driver") != "paper_rounds" or not work.get("rounds"):
        return None
    flops = work["flops_per_round"] * work["rounds"]
    return 100.0 * flops / (summary.window_s * summary.chips
                            * peaks["bf16_flops_per_s"])
