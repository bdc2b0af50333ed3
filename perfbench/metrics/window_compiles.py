"""Programs lowered (then compiled or read from the compile cache) inside the
window; every shape the window meets should have been warmed up."""


def read(w):
    return w.compiles
