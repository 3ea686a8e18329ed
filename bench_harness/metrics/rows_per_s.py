"""Dataset rows (each with all its features) whose histograms were built
and allreduced, over every round of the window, per second of the
window's wall time."""


def read(run):
    if run.entry != "histogram":
        return None
    return sum(r["rows"] for r in run.records) / run.window_s
