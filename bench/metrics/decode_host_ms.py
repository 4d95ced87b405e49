"""Mean, over decode steps with no refill between, of the device's idle
from the end of one step's last program (the decode, its argmax and
finite check) to the start of the next ``jit_decode_step``, on the
device's clock: the device idle the host loop costs each step."""
from .. import serve_trace


def read(run):
    r = serve_trace.for_run(run)
    if r is None or not r.decode_host_s:
        return None
    return 1e3 * sum(r.decode_host_s) / len(r.decode_host_s)
