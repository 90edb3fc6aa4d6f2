"""Requests answered within their deadline, over the whole window, per
second: every answer that arrived inside the window counts (the engine
fails a request past its deadline, so an answer is within it)."""
from traffic import OK


def read(run):
    log = run.log
    done = (log.outcome == OK) & (log.t_done <= run.t_end)
    return float(done.sum()) / run.seconds
