"""Summed device time of the hop megakernel's events per execution of the
search program, in ms."""


def read(run):
    runs = run.trace.search_runs() if run.trace else []
    hops = run.trace.hop_ops() if runs else []
    if not hops:
        return None
    return sum(e[4] for e in hops) * 1e-6 / len(runs)
