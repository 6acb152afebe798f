"""The paper's AXPYDOT as a user of the compiler writes it (Table 1 of
arXiv:2212.13768): ``result = dot(axpy(a, x, y), w)``."""
from repro.frontends import blas
from repro.frontends.api import Program


def build(n: int):
    p = Program("axpydot")
    a = p.scalar_input("a", "float32")
    x, y, w = (p.input(nm, (n,)) for nm in ("x", "y", "w"))
    p.output("result", blas.dot(blas.axpy(a, x, y), w))
    return p.finalize()
