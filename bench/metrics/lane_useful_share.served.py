"""Lane-iterations the requests needed over those the engine executed
(vmapped lanes run in lockstep), summed over the window's waves."""


def read(run):
    done = run.counters.get("executed_inner", 0)
    return 100.0 * run.counters["useful_inner"] / done if done else None
