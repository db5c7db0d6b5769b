"""Process start to the window's start (host clock): imports, chip start-up,
weights, planning, warm-up and compiles or cache loads."""


def read(run):
    return run.setup["setup_s"]
