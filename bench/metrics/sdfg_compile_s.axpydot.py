"""sdfg_compile_s.axpydot: seconds the staged compiler took to build
AXPYDOT before the window opened: the outermost ``repro.frontend``,
``repro.lower``, ``repro.optimize`` and ``repro.compile`` spans."""
from bench.spans import sdfg_compile_s as read  # noqa: F401
