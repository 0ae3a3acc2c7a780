"""Mean share of the slot engine's slots live in a decode step, over the
window's steps: the server's ``occupancy_sum`` over its ``steps``."""


def read(run):
    if run.kind != "serve":
        return None
    steps = run.stats1["steps"] - run.stats0["steps"]
    if steps <= 0:
        return None
    occ = run.stats1["occupancy_sum"] - run.stats0["occupancy_sum"]
    return 100.0 * occ / steps
