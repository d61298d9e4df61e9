"""Least time for the row half-step kernel's bytes (`gw_sinkhorn_row`,
one read of the (M, N) float32 cost per Sinkhorn sweep, the pass table of
bench/roofline.py), at peak HBM bandwidth, as a share of the kernel's
device seconds in the traced window's breakdown."""
from bench import roofline

KERNEL = "%gw_sinkhorn_row"


def read(run):
    seconds = dict(getattr(run.trace, "device_ops", ())).get(KERNEL)
    inner = run.counters.get("inner_iters")
    if not seconds or not inner:
        return None
    n = int(run.cell.config["geometry"]["side"]) ** 2
    return roofline.hbm_share(roofline.F32_BYTES * n * n * sum(inner),
                              seconds, run.peaks)
