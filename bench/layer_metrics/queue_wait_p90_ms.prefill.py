"""90th percentile of the wait in front of the prefill stage (submit to
engine admission, the program's ``Request.queue_delays``), in ms, over
the requests due in the window."""
import numpy as np


def read(run):
    waits = [sum(r.req.queue_delays["prefill"]) for r in run.records
             if r.req.queue_delays.get("prefill")]
    return float(np.percentile(waits, 90)) * 1e3 if waits else None
