"""Mean recall@10 of the sampled answers against the exact reference, as
the comparison that decides ``correct`` computed it."""


def read(run):
    return run.checks["recall_at_10"]["value"]
