"""prefill_device_share.chat: Percent of the chat window's device busy time spent outside the compiled
decode step: batch-1 prefill programs and page scatters."""
from bench.readers import prefill_device_share as read  # noqa: F401
