"""Set-up's seconds under the program's ``bin.bundle_plan`` and
``bin.bundle_build`` spans, which the driver keeps as a counter."""


def read(run):
    return run.counters.get("bundle_s")
