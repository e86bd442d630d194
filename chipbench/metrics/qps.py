"""qps: requests answered inside the window over the window's length."""


def read(run):
    return run.n_answered_in_window / run.seconds
