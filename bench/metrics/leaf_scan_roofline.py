"""Leaf dominance scan's share of its roofline (%), from the device trace.

On the served path (stacked probe, device join) the leaf scan is the
stacked probe's jitted pair stage, the program ``pairs`` of
``dist/probe.py``: pair expansion, the exact label and dominance compares,
the tombstone filter and the candidate gather.  Its least time is the
scan's bytes (``gnnbench/roofline.py``: the leaf pairs the funnel counted
in the traced window at the configuration's unpadded widths) over the
chip's HBM bandwidth; the measured time is the summed device time of that
program's runs in the trace.  The scan is bound by bytes, not by compares.
"""
from gnnbench import roofline, tracefile

PROGRAM = "jit_pairs"


def read(run):
    t = run.trace
    if t is None:
        return None
    seconds = tracefile.op_seconds(t, lambda n: n.split("(")[0] == PROGRAM, "module_ns")
    e = run.cell.config["engine"]
    pairs = run.counter("gnnpe_funnel_total", stage="leaf_pairs")
    nbytes = roofline.leaf_scan_bytes(pairs, e["path_length"], e["emb_dim"], e["n_multi"])
    peak = run.peaks[run.device["kind"]]
    return roofline.roofline_share(nbytes, seconds, peak)
