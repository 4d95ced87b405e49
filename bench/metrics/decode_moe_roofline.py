"""Least time of the decode steps' MoE layers (the family module's
``moe_decode_work``) over the self time of the operations under the
``moe`` scope in those steps' executions, over the traced decodes: the
expert layer's share of its roofline."""
from .. import reference, serve_trace


def read(run):
    r = serve_trace.for_run(run)
    work = getattr(reference.family(run.conf["reference"]), "moe_decode_work",
                   None)
    pairs = run.with_device_times("decode")
    if r is None or work is None or not pairs or not r.scope_s["decode"].get("moe"):
        return None
    least = sum(work(run.conf, l.rows).least_s(run.peak) for l, _ in pairs)
    return 100.0 * least / r.scope_s["decode"]["moe"]
