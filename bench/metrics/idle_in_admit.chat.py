"""idle_in_admit.chat: percent of the chat window's device idle time in
which the host was inside an admission (``repro.admit``), from the
profiler trace."""


def read(run):
    from bench.spans import idle_in
    return idle_in(run, "admit")
