"""Host milliseconds per sync inside ``engine.step``: over the program's
``serve.step`` spans wholly inside the traced window, the mean of each
step's time outside its ``serve.sync`` (the wait for the device loop and the
fetch of its outputs).  Read from the run's trace file (``bench/spans.py``);
a program that records no ``serve.*`` spans gives nothing."""
from bench.run import OUT
from bench.spans import host_ms_per_sync, host_spans
from bench.trace import find_xplane, load


def read(run):
    (lo, hi), spans = host_spans(load(find_xplane(str(OUT / "trace"))))
    return host_ms_per_sync(spans, lo, hi)
