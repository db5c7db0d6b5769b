"""Images whose logits reached the host inside the window, per second of
window (host clock)."""


def read(run):
    return len(run.done_between(run.t0, run.t1)) / run.seconds
