"""Set-up seconds: process start to the window's first timed step
(imports, weights, planning, compiles or cache loads, warm-up, and a
closed loop's first fill)."""


def read(run):
    return run.setup_s
