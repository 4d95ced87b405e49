"""Mean, over decode steps with no refill between, of the time from the
end of the engine's ``serve.sync`` span (tokens on the host) to the start
of the next ``jit_decode_step`` execution on the device: the device idle
the host loop costs each step."""
from .. import serve_trace


def read(run):
    r = serve_trace.for_run(run)
    if r is None or not r.decode_host_s:
        return None
    return 1e3 * sum(r.decode_host_s) / len(r.decode_host_s)
