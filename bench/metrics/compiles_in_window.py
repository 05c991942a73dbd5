"""Executables needed inside the window (count): each backend-compile
event, whether compiled or read from the persistent cache, counted by
``jax.monitoring``."""


def read(win):
    return float(win.compiles)
