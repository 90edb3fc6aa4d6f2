"""Mean of a flush's two thread hand-offs through ``asyncio.to_thread``, in
ms: ``serve.form``'s end to ``serve.prep``'s start, plus ``serve.map``'s
end to ``serve.post``'s start."""
import spans


def read(run):
    fl = spans.flushes(run.trace) if run.trace else []
    if not fl:
        return None
    return sum(b - a for f in fl for a, b in spans.handoffs(f)) \
        * 1e-6 / len(fl)
