"""Share of the window's queries of which a chain ran on the device."""


def read(run):
    qs = run["queries"]
    if not qs:
        return None
    return 100.0 * sum(q["digest"]["engine"] == "device" for q in qs) / len(qs)
