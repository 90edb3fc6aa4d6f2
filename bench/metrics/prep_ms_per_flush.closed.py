"""The worker thread's host work in the window per execution of the search
program, in ms: ``serve.prep`` (stacking and rank mapping),
``serve.launch`` (padding, copies, the program's enqueue) and
``serve.map`` (original ids)."""
import spans


def read(run):
    return spans.per_search_run(
        run.trace, (spans.PREP, spans.LAUNCH, spans.MAP)) if run.trace \
        else None
