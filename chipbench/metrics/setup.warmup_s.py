"""Host clock around `engine.warmup(<the cell's buckets>)`: the plan cache
building or loading one program per bucket."""


def read(run):
    return run.setup["warmup_s"]
