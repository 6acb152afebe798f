"""padding_lane_share.chat: percent of the decode steps' lanes in the
chat window that carried no request (``sched.lanes`` against
``sched.live_lanes``)."""
from bench.spans import padding_lane_share as read  # noqa: F401
