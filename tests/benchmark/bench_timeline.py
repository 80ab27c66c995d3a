"""What the benchmark's tests that drive a cluster share: a timeline that
starts with their own cluster.  (Not a conftest.py: the suite's other files
import tests/conftest.py by that name.)"""

import contextlib


@contextlib.contextmanager
def timeline_from_here():
    """``benchmark.spans.timeline()`` with the client rows written from now
    on only.  A benchmark run is one process and one window, so
    ``spans.window`` opens it at the process's second ``start`` row; a test
    process has driven other clusters before this one."""
    from benchmark import spans
    from minbft_tpu.obs import trace

    mark = len(trace.timeline()["client"]["rows"])
    whole = spans.timeline

    def own() -> dict:
        tl = whole()
        tl["client"]["rows"] = tl["client"]["rows"][mark:]
        return tl

    spans.timeline = own
    try:
        yield
    finally:
        spans.timeline = whole
