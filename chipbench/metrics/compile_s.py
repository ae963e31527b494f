"""Host seconds of every program's first call during set-up, less its
planning: compiles, or loads from JAX's persistent cache."""


def read(run):
    if run.plan_s is None:
        return None
    return run.first_call_s - run.plan_s
