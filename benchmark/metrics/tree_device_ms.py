"""Device time per tree: the union of device-op intervals in the traced
window on the busiest device, over the trees the window returned."""


def read(run):
    if run.trace is None or not run.work.get("trees"):
        return None
    return run.trace["busiest_busy_s"] * 1e3 / run.work["trees"]
