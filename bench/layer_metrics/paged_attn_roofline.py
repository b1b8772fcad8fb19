"""Share of its roofline the paged decode kernel reaches, in %: the least
time the chip could take for the kernel calls in the traced slice (each
call's FLOPs over the bf16 peak or its needed bytes over HBM bandwidth,
whichever is larger; ``bench/counts.py``) over their device time.  The
kernel's events are the ``paged_attention`` custom calls, one per layer
of each decode; their needed work is the mean of the decode calls the
host made in the slice."""
from bench import counts
from bench import trace as T


def read(run):
    if run.trace is None:
        return None
    kernel = T.op_durations(run.trace, "paged_attention", run.trace_window)
    h0, h1 = run.trace_host
    page = run.cell.config["serving"]["page_size"]
    per_call = [counts.roofline_seconds(
        counts.paged_kernel_flops(run.dims, lens),
        counts.paged_kernel_bytes(run.dims, lens, page), run.peaks)
        for calls in run.recorder.decodes.values()
        for s, _, lens in calls if h0 <= s < h1]
    if not kernel or not per_call:
        return None
    need = sum(per_call) / len(per_call) * len(kernel)
    return 100.0 * need / sum(kernel)
