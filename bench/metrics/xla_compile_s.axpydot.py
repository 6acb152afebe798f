"""xla_compile_s.axpydot: seconds of JAX's trace, lowering (Pallas to
Mosaic included) and backend compile or persistent-cache load under
AXPYDOT's ``repro.call`` spans before the window opened."""
from bench.spans import xla_compile_s as read  # noqa: F401
