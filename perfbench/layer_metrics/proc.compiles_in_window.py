"""Programs compiled, fetched from the compile cache or retraced between
the window's opening and its close (jax's compile events and the program's
``recompile.trace_counts``). Anything but 0 also fails ``correct``."""


def read(run):
    return run["facts"].get("compiles_in_window")
