"""Process start to window open: imports, building the weights, the
reference check, warm-up (with compilation in a run that compiles) and the
uncounted lead-in of traffic."""


def compute(run):
    return run["setup_s"]
