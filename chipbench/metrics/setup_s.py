"""setup_s: process start to the first timed request (s): corpus, build
(compile included on a cold cache), runtime warm-up."""


def read(run):
    return run.setup_s
