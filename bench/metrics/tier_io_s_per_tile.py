"""Host seconds inside the tiered store's spans (``rt.tier.*``: gets, puts,
promotions, demotions), counted once where they nest, summed over threads,
per tile finished in the window (s/tile).  Staging puts count too."""
from bench import spans
from bench.readers import delta, ratio


def read(run):
    seconds = (spans.read(run) or {}).get("groups", {}).get("rt.tier")
    return None if seconds is None else ratio(seconds, delta(run, "tiles"))
