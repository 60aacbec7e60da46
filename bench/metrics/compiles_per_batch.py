"""Backend compiles, persistent-cache reads included, in the window per
published batch (JAX's backend_compile_duration events)."""


def read(run):
    return run["compiles_in_window"] / run["units"] if run["units"] else None
