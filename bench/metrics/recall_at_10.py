"""recall@10 of the window's answers against the exact top-10."""


def read(run):
    return run.check["recall"] if run.check["recall_answers"] else None
