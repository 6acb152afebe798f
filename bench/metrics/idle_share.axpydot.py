"""idle_share.axpydot: Percent of the traced AXPYDOT window in which no device operation ran."""
from bench.readers import idle_share as read  # noqa: F401
