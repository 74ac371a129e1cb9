"""One reader per metric: ``read(run)`` returns the metric's value, or
None where the run holds nothing to read (the harness then leaves the
metric out of the result line).

``run`` carries the cell's configuration (``cfg``), the window
(``window``: host-clock start and end, ``seconds``), every call the driver
made (``calls``), the driver's counters (``counters``), ``setup_s``, the
device's peaks (``peak``) and, in a traced run, the reduced trace
(``trace``, see ``bench/tracing.py``).
"""
from bench import tracing


def device_seconds(run, program: str, span: str):
    """(device seconds, executions) of the programs whose name holds
    ``program`` and that were launched inside ``span``."""
    secs, n = 0.0, 0
    for (name, where), (s, k) in tracing.program_seconds(run.trace).items():
        if program in name and where == span:
            secs += s
            n += k
    return secs, n
