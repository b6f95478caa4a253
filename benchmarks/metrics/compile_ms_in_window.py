"""jax trace + lower + backend-compile time inside the window, as the
agent's `jax_compile` spans record it; anything but 0 means the warm-up
missed a shape or a program is traced anew."""
from _spans import ms, window_spans


def read(run):
    spans = window_spans(run)
    if spans is None:
        return None
    return float(sum(ms(s) for s in spans if s.name == "jax_compile"))
