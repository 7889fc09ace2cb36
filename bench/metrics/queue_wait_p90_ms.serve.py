"""90th percentile, over the requests whose prefill was launched in the
traced window, of the milliseconds from submit to the launch: the wait
for a lane (``queue_ms``) plus the wait in its lane for the join
(``join_wait_ms``). Both are whole milliseconds of the program's host
clock, carried on its ``serve.request`` events. Like TTFT, it follows
how the seed's arrivals bunch."""
import numpy as np

from bench import spans


def read(run, ctx):
    got = spans.of_run(run, ctx)
    if got is None or not got["each"].get("serve.request"):
        return None
    waits = [s["queue_ms"] + s["join_wait_ms"]
             for s in got["each"]["serve.request"]]
    return float(np.percentile(np.asarray(waits, np.float64), 90))
