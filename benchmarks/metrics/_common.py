"""Shared by the readers: the queries of the traced span, and the rows in a
query's time range."""


def touching_trace(run: dict) -> list:
    """Queries any part of which lies inside the traced span."""
    t = run["trace"]
    if not t:
        return []
    return [q for q in run["queries"]
            if q["t0_unix_ns"] + q["wall_ms"] * 1e6 > t["lo_unix_ns"]
            and q["t0_unix_ns"] < t["hi_unix_ns"]]


def rows_in_range(run: dict, q: dict) -> int:
    """Rows of the script's table at or after the query's start_time."""
    import numpy as np

    from data import table_rows, time_step_ns

    table = run["scripts"][q["script"]]["table"]
    spec = next(t for t in run["config"]["tables"] if t["name"] == table)
    rows = table_rows(run["config"], spec)
    step = time_step_ns(run["config"], rows)
    first = -(-(q["start_time"] - int(run["config"]["time_base_ns"])) // step)
    return int(np.clip(rows - first, 0, rows))
