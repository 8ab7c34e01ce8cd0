"""Backend compilations inside the window: the program's
``get_profiler().compile_seq()`` after the window less before it."""


def read(run):
    return run.counters.get("compiles_in_window")
