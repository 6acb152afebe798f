"""idle_share.chat: Percent of the traced chat window in which no device operation ran."""
from bench.readers import idle_share as read  # noqa: F401
