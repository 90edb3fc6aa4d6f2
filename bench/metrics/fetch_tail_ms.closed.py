"""Mean, over executions of the search program, of the time from the
program's end on the device to the end of the ``serve.fetch`` that
returned its results to the host, in ms: the ready signal, the wait's
wake-up and the device-to-host copy, on the trace's one clock."""
import spans


def read(run):
    pairs = spans.fetch_of_runs(run.trace) if run.trace else []
    if not pairs:
        return None
    return sum(f[3] + f[4] - (r[3] + r[4]) for r, f in pairs) \
        * 1e-6 / len(pairs)
