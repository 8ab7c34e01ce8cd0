"""Host time per tree: the traced window's seconds less the device-busy
union on the busiest device, over the trees the window returned."""


def read(run):
    if run.trace is None or not run.work.get("trees"):
        return None
    idle = run.trace["window_s"] - run.trace["busiest_busy_s"]
    return idle * 1e3 / run.work["trees"]
