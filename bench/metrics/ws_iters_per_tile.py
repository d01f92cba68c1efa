"""The nucleus split's fixed-point loops: kernel calls until convergence
over the window, per tile (the program's own report, ``split_nuclei``'s
"split"); None where the program reports none."""
from bench.readers import delta, ratio


def read(run):
    if "split_calls" not in run.counters["after"]:
        return None
    return ratio(delta(run, "split_calls"), delta(run, "tiles"))
