"""Least HBM bytes that one dense-plan GW solve must move.

Counted from the shapes and the solve's own ``ConvergenceInfo`` counts, at
the configuration's stated float32 (4 bytes an entry), for the work the
algorithm needs and not the work one implementation happens to do.  Per
solve with T outer steps, I Sinkhorn sweeps in all and residual checks
every ``chunk`` sweeps, each pass one read or write of an (M, N) array:

    gradient        C = C1 − 4 D_X Γ D_Y: read Γ, write C        2 T
    Sinkhorn        each half-step reads C once                  2 I
    residual        each check reads C once (plan formed on
                    the fly and row-summed)                      ceil(I / chunk)
    plan update     write the new Γ (fused with the last check)  T
    plan change     read the old Γ                               T
    value           read the final Γ                             1

The (M,)- and (N,)-sized vectors are left out.  So are the initial product
plan, which needs no read, and the cost tiles' storage precision: a change
to 16-bit tiles halves the bytes moved but not the bytes counted here.
"""
from __future__ import annotations

F32_BYTES = 4


def solve_passes(outer: int, inner: int, chunk: int) -> int:
    """(M, N)-array passes of one solve; see the module docstring."""
    if outer < 0 or inner < 0 or chunk < 1:
        raise ValueError(f"bad counts outer={outer} inner={inner} "
                         f"chunk={chunk}")
    checks = -(-inner // chunk)
    return 2 * outer + 2 * inner + checks + outer + outer + 1


def solve_bytes(m: int, n: int, outer: int, inner: int, chunk: int) -> int:
    """Least bytes one (m, n) dense-plan solve moves at float32."""
    return F32_BYTES * m * n * solve_passes(outer, inner, chunk)


def hbm_share(total_bytes: float, seconds: float, peaks: dict) -> float:
    """Least time to move ``total_bytes`` at peak HBM bandwidth, as a
    percentage of ``seconds``."""
    return 100.0 * total_bytes / peaks["hbm_bytes_per_s"] / seconds
