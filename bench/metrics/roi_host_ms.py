"""Mean duration of the ``ObjectROIs`` task (``rt.task/ObjectROIs``: label
download, bounding boxes and host crops) in the window (ms)."""
from bench import spans


def read(run):
    row = (spans.read(run) or {}).get("spans", {}).get("rt.task/ObjectROIs")
    return 1e3 * row["seconds"] / row["calls"] if row else None
