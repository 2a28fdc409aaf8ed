"""XLA compiles inside the window, as JAX's monitoring events count
them.  Anything above 0 is a shape or an eager operation that set-up did
not warm."""


def read(res):
    return res["compiles_window"]
