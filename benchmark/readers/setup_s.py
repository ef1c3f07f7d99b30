"""Process start of the runner to the first timed request or step: weights,
the program's start, compile or cache load, warm-up requests."""


def read(ctx, run, params):
    return run["setup_s"]
