"""Self time of the operations under the ``attention`` or ``ssd`` scope
inside the ``jit_prefill_step`` executions, over those executions' device
time."""
from .. import serve_trace


def read(run):
    r = serve_trace.for_run(run)
    return None if r is None else r.mixer_share("prefill")
