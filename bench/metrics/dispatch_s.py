"""Host seconds per step inside the program's ``dbcsr.dispatch`` spans:
the schedule engine's call, from its closure through trace, lowering and
the compile-cache lookup to the return of the enqueue."""
from bench import spans


def read(r, path):
    return spans.seconds_per_step(r, "dbcsr.dispatch")
