"""dispatch_idle_share.femnist: the share of the traced window in which
the chip is idle while the round driver dispatches the jitted round
step (the program's `fedmeta.round.dispatch` span), in %, averaged over
the chips (benchlib/program_spans.py)."""
from benchlib import program_spans


def read(summary, work, peaks):
    if work.get("driver") != "paper_rounds":
        return None
    split = program_spans.idle_split(summary)
    return None if split is None else split["dispatch"]
