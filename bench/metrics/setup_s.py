"""Set-up: from the process's start to the first timed call (loading,
operands, warm-up and, in a run that compiles, compilation)."""


def read(r, path):
    return r.setup_s
