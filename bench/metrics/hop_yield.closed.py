"""Share of the candidate slots of the expanded nodes that became a newly
gathered row: the engine's ``rows_gathered`` (the rows whose distances the
search computed) over ``nodes_expanded`` times m_out."""


def read(run):
    s = run.window_stats
    if not s.get("nodes_expanded") or "rows_gathered" not in s:
        return None
    return s["rows_gathered"] / (s["nodes_expanded"] * run.hop_shape()[2])
