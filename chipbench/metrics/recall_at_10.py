"""recall_at_10: mean recall@10 of every answered request against the
exact top-10 (chipbench/reference.py, HIGHEST precision)."""


def read(run):
    return run.recall
