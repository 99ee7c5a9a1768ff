"""run_overhead_idle_share.femnist: the share of the traced window in
which the chip is idle inside a `FederatedTrainer.run` call (the
program's `fedmeta.run` span) but in no stage, prefetch-wait or
dispatch span: the per-round loss drain (`fedmeta.round.flush`), the
call's set-up and the driver's bookkeeping, in %, averaged over the
chips (benchlib/program_spans.py). With the stage and dispatch shares
and the idle time outside `fedmeta.run`, it makes up
`device_idle_share.femnist`."""
from benchlib import program_spans


def read(summary, work, peaks):
    if work.get("driver") != "paper_rounds":
        return None
    split = program_spans.idle_split(summary)
    return None if split is None else split["run_overhead"]
