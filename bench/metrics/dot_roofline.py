"""Share of its roofline of the densified path's local product (the
``lax.dot`` of ``core/densify.py``), from the device ops that the
profiler files under the HLO category of a dot: on the TPU the dot runs
as an output fusion (``%fusion.N = ... kind=kOutput``) of the category
"convolution fusion", and no op carries "dot" in its name; a named scope
in the program would give it one."""
import re

from bench.metrics import kernel_roofline

ANY = re.compile("")
DOT = re.compile(r"^convolution( fusion)?$")


def read(r, path):
    return kernel_roofline(r, ANY, DOT)
