"""Set-up's seconds of tracing and backend compile in the warm-up fit:
the ``jaxpr_trace_s`` and ``backend_compile_s`` of its ``train.build_step``
and ``train.launch`` spans, which the driver keeps as a counter."""


def read(run):
    return run.counters.get("warmup_compile_s")
