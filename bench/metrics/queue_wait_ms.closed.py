"""Mean time a dispatched request waited in the serving loop's queue, from
its submit to the take that formed its batch, in ms (the engine's
``queue_wait_s`` over ``dispatched``)."""


def read(run):
    s = run.window_stats
    if "queue_wait_s" not in s or not s.get("dispatched"):
        return None
    return 1e3 * s["queue_wait_s"] / s["dispatched"]
