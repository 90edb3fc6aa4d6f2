"""Hop megakernel events per execution of the search program: the beam
iterations until the slowest query of the batch stops."""


def read(run):
    runs = run.trace.search_runs() if run.trace else []
    hops = run.trace.hop_ops() if runs else []
    if not hops:
        return None
    return len(hops) / len(runs)
