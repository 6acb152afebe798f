"""compiles_in_window.chat: programs compiled or loaded from the
persistent cache while the chat window ran (a shape the warm-up missed).
"""


def read(run):
    return run.compiles_in_window
