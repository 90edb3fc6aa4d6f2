"""Mean device gap between one search program's end and the next one's
start, in ms: host batch preparation, the executor call and the loop's
hand-off between flushes."""


def read(run):
    gaps = run.trace.flush_gaps_s() if run.trace else []
    if not gaps:
        return None
    return 1e3 * sum(gaps) / len(gaps)
