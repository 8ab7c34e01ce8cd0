"""1 minus busy over the traced window, busiest device, in percent."""


def read(run):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busiest_busy_s"] / run.trace["window_s"])
