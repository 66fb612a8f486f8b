"""Share of its roofline of the blocked path's small-matrix-multiply
kernel (the Pallas ``kernels/smm`` custom call), from its operations in
the device trace."""
import re

from bench.metrics import kernel_roofline

SMM = re.compile(r"smm")
CUSTOM_CALL = re.compile(r"^custom-call$")


def read(r, path):
    return kernel_roofline(r, SMM, CUSTOM_CALL)
