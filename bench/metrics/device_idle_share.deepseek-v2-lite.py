"""device_idle_share.deepseek-v2-lite: the share of the traced window in
which no executable runs on a chip (1 - busy / window, averaged over the
chips), in %, for the DeepSeek-style LM meta-training cells."""


def read(summary, work, peaks):
    if work.get("driver") != "lm_train_moe":
        return None
    return 100.0 * (1.0 - summary.busy_s / summary.window_s)
