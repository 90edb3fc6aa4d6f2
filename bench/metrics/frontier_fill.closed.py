"""Share of the frontier slots the hop launches that expanded a node: the
nodes expanded a flush (the engine's ``nodes_expanded`` over ``flushes``)
over hop launches a flush (the trace) times B * W, the slots of one
launch."""


def read(run):
    s = run.window_stats
    runs = run.trace.search_runs() if run.trace else []
    hops = run.trace.hop_ops() if runs else []
    if not hops or not s.get("nodes_expanded") or not s.get("flushes"):
        return None
    B, W = run.hop_shape()[:2]
    return (s["nodes_expanded"] / s["flushes"]) \
        / (len(hops) / len(runs) * B * W)
