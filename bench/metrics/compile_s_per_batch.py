"""Seconds of tracing, lowering and backend compiles (persistent-cache
reads included) in the window per published batch: what the program's
rebuild of its shard program costs a batch (JAX's compile-duration
events)."""


def read(run):
    return (run["compile_s_in_window"] / run["units"] if run["units"]
            else None)
