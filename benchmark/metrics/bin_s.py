"""Host binning: the driver's clock around fit_bin_mapper + transform_packed."""


def read(run):
    return run.counters.get("bin_s")
