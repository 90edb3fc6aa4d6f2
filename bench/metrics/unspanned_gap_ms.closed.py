"""Mean, over the device gaps between one search program and the next, of
the time covered by no ``serve.*`` or ``bench.*`` span on any thread and by
no hand-off of a flush, in ms."""
import spans


def read(run):
    fl = spans.flushes(run.trace) if run.trace else []
    gaps = spans.unspanned_gaps_ns(run.trace) if fl else []
    if not gaps:
        return None
    return sum(gaps) * 1e-6 / len(gaps)
