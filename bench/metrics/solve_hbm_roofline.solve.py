"""Least time for the bytes the window's solves had to move, at peak HBM
bandwidth, as a share of the window (see bench/roofline.py)."""
from bench import roofline


def read(run):
    b = run.counters.get("solved_bytes")
    return roofline.hbm_share(b, run.window_s, run.peaks) if b else None
