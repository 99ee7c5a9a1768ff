"""device_idle_share.femnist: the share of the traced window in which no
executable runs on a chip (1 - busy / window, averaged over the chips),
in %, for the paper-round cells. The result line's breakdown names the
host span each idle gap fell in."""


def read(summary, work, peaks):
    if work.get("driver") != "paper_rounds":
        return None
    return 100.0 * (1.0 - summary.busy_s / summary.window_s)
