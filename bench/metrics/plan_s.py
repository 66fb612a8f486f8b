"""Host seconds per step inside the program's ``dbcsr.plan`` spans: the
on-the-fly norms, masks, occupancy and the planner."""
from bench import spans


def read(r, path):
    return spans.seconds_per_step(r, "dbcsr.plan")
