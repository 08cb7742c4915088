"""Process start to the window's start: data, indexes, warm-up and, in a
run that compiles, compilation. Host clock."""


def compute(run):
    return run["setup_s"]
