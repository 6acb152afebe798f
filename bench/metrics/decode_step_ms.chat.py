"""decode_step_ms.chat: Mean device time of one execution of the compiled decode step in the
chat window, in milliseconds."""
from bench.readers import decode_step_ms as read  # noqa: F401
