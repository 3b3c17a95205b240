"""Executables compiled or loaded from the persistent compilation cache
while the window ran (JAX's backend-compile monitoring event). Set-up
warms every launch shape the traffic uses, so this should read 0."""


def read(run):
    return float(run.compiles_in_window)
