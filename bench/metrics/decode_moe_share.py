"""Self time of the operations under the ``moe`` scope inside the
``jit_decode_step`` executions, over those executions' device time."""
from .. import serve_trace


def read(run):
    r = serve_trace.for_run(run)
    if r is None or not r.step_s.get("decode") or "moe" not in r.scope_s["decode"]:
        return None
    return 100.0 * r.scope_s["decode"]["moe"] / r.step_s["decode"]
