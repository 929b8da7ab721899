"""set-up: the first statement of each class, summed: transfer, compile or
cache read, the chunk estimator's cold start."""


def read(run):
    return run["setup"].get("first_stmt_s")
