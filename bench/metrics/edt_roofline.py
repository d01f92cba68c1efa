"""The squared distance transform against its least bytes: int32 mask in,
int32 squared distances out, once each per call (%)."""
from bench import counts_ws
from bench.readers import roofline_pct


def read(run):
    t = run.config["tile"]
    return roofline_pct(run, ("jit_distance_transform",), counts_ws.edt_bytes(t, t))
