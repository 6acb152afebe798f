"""attn_roofline.chat: The attention grid kernels' share of their roofline in the chat window:
the least time the live lanes' attention needs over the kernels' device
time, in percent."""
from bench.readers import attention_roofline as read  # noqa: F401
