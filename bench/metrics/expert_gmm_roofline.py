"""expert_gmm_roofline: the held experts' grouped matrix products
against their roofline, in %: the sum of the least times of the
`expert_gmm` and `expert_tgmm` calls in the traced window over the sum
of their device times.

Every call of one MoE layer in one pass is a (pairs x d) by (d x Fe)
product or its like (forward, recompute, input and weight gradients).
Its least time is the larger of its FLOPs over the chip's bf16 peak and
its least bytes over the HBM bandwidth, both from the configuration's
`expert_call_cost` (its reference module) at the window's mean pairs per
layer and pass, from the step's `moe_pairs_held` counter. The kernels
are found by the names the program gives their `pallas_call`s; a call
the trace does not name so is not counted."""
import re

KERNEL = re.compile(r"^expert_t?gmm(\.\d+)?$")


def is_expert_kernel(hlo: str) -> bool:
    if 'custom_call_target="tpu_custom_call"' not in hlo:
        return False
    return bool(KERNEL.match(hlo.partition(" = ")[0].strip().lstrip("%")))


def read(summary, work, peaks):
    if work.get("driver") != "lm_train_moe" or not work.get("rounds"):
        return None
    least_call = max(work["expert_call_flops"] / peaks["bf16_flops_per_s"],
                     work["expert_call_bytes"] / peaks["hbm_bytes_per_s"])
    calls, spent = 0, 0.0
    for op in summary.ops:
        if is_expert_kernel(op.name):
            calls += 1
            spent += op.end - op.start
    if spent <= 0.0:
        return None
    return 100.0 * calls * least_call / spent
