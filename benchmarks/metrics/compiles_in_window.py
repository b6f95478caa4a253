"""Backend compiles (or cache retrievals) that jax reported inside the
window; anything but 0 means the warm-up missed a shape."""


def read(run):
    return float(run["compiles_in_window"])
