"""Mean bytes uploaded per device-routed query (0 where all is resident)."""


def read(run):
    xs = [q["digest"]["h2d_bytes"] for q in run["queries"]
          if q["digest"]["engine"] == "device"]
    return sum(xs) / len(xs) if xs else None
