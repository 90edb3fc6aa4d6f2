"""The event loop's spanned work in the window per execution of the search
program, in ms: ``serve.form``, ``serve.post``, ``serve.admit`` and
``serve.reap``."""
import spans


def read(run):
    return spans.per_search_run(run.trace, spans.LOOP) if run.trace \
        else None
