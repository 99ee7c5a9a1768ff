"""compiles_in_window.deepseek-v2-lite: executables the program compiled
(or fetched from the persistent compile cache) inside the traced window
of the DeepSeek-style LM meta-training cells: the program's
`fedmeta.compile` markers there (benchlib/program_spans.py). Set-up
compiles the step ahead of time, so it should read 0."""
from benchlib import program_spans


def read(summary, work, peaks):
    if work.get("driver") != "lm_train_moe":
        return None
    return program_spans.compiles_in_window(summary)
