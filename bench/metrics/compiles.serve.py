"""Lowerings to XLA (compiles or loads from the persistent cache) inside
the window, counted by the harness's listener; 0 when set-up warmed every
program the window runs."""


def read(run, ctx):
    return run.counters.get("compiles")
