"""Mean device time of one execution of the engine's prefill program."""


def read(run):
    t = run.device_times("prefill")
    return 1e3 * sum(t) / len(t) if t else None
