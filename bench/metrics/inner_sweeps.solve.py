"""Mean Sinkhorn sweeps per solve, from each solve's ConvergenceInfo."""


def read(run):
    xs = run.counters.get("inner_iters")
    return sum(xs) / len(xs) if xs else None
