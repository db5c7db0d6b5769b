"""Host clock around the `Engine(...)` construction, which runs
`plan_network` on the calibration images (the planner)."""


def read(run):
    return run.setup["plan_s"]
