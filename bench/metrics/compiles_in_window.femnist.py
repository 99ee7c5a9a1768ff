"""compiles_in_window.femnist: executables the program compiled (or
fetched from the persistent compile cache) inside the traced window of
the paper-round cells: the program's `fedmeta.compile` markers there
(benchlib/program_spans.py). Set-up warms every shape, so it should
read 0."""
from benchlib import program_spans


def read(summary, work, peaks):
    if work.get("driver") != "paper_rounds":
        return None
    return program_spans.compiles_in_window(summary)
