"""Mean device time of one execution of the warmed search program, in
ms."""


def read(run):
    runs = run.trace.search_runs() if run.trace else []
    if not runs:
        return None
    return sum(e[4] for e in runs) * 1e-6 / len(runs)
