"""Least time for the bytes the window's solved requests had to move (each
at its own unpadded size and ConvergenceInfo counts; cache hits moved
none), at peak HBM bandwidth, as a share of the window."""
from bench import roofline


def read(run):
    b = run.counters.get("solved_bytes")
    return roofline.hbm_share(b, run.window_s, run.peaks) if b else None
