"""Share of the chips' busy time in which a collective op ran (the
occupancy all-reduce of a data-parallel batch): collective seconds over
busy seconds, both summed over the cell's chips (profiler trace,
`trace.is_collective`). Nothing to read where no collective op ran."""


def read(run):
    tr = run.trace
    if tr is None or tr["busy_s"] <= 0 or tr["collective_s"] <= 0:
        return None
    return 100.0 * tr["collective_s"] / (tr["busy_s"] * tr["devices"])
