"""Programs JAX lowered inside the window (each a compile or a
persistent-cache load); 0 when set-up warmed up every shape."""


def read(run):
    return run.window_compiles
