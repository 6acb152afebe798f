"""admit_ms.chat: mean duration of the scheduler's admissions
(``repro.admit``: prefill, page scatter, first token) in the chat
window, in milliseconds."""


def read(run):
    from bench.spans import mean_ms
    return mean_ms(run, "admit")
