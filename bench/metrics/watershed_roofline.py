"""The watershed (cost reconstruction, label propagation, relabelling)
against its least bytes: int32 squared distances and marker labels in,
int32 labels out, once each per call (%)."""
from bench import counts_ws
from bench.readers import roofline_pct


def read(run):
    t = run.config["tile"]
    return roofline_pct(run, ("jit_watershed",), counts_ws.watershed_bytes(t, t))
