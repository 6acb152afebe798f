"""axpydot_roofline: the fused Axpy+Dot kernel's share of its roofline:
``calls * 12 n bytes / HBM bandwidth`` over the kernel's device time in
the traced window, in percent."""
import re

from bench.program import roofline_share

KERNEL = re.compile(r"axpydot", re.IGNORECASE)


def read(run):
    return roofline_share(run, lambda s: bool(KERNEL.search(s.name)))
