"""stage_idle_share.femnist: the share of the traced window in which the
chip is idle while the round driver stages a round block, in %: idle
time inside the program's `fedmeta.round.stage` span (host task
sampling `fedmeta.round.sample` and the `device_put`s
`fedmeta.round.put` within it) or, with prefetching, its wait for the
staged block (`fedmeta.round.prefetch_wait`), averaged over the chips
(benchlib/program_spans.py)."""
from benchlib import program_spans


def read(summary, work, peaks):
    if work.get("driver") != "paper_rounds":
        return None
    split = program_spans.idle_split(summary)
    return None if split is None else split["stage"]
