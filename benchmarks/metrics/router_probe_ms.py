"""Mean wall of the window's probes: the queries in which the router
explored its other arm (stats.mark_probes), whether the explored arm
answered or the executor backed out of it."""


def read(run):
    xs = [q["wall_ms"] for q in run["queries"] if q["digest"]["probe"]]
    return sum(xs) / len(xs) if xs else None
