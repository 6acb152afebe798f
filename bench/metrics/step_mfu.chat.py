"""step_mfu.chat: Model FLOPs of the tokens the chat window produced, over the window times
the chip's bf16 peak, in percent."""
from bench.readers import step_mfu as read  # noqa: F401
