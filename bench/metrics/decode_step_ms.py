"""Mean device time of one execution of the engine's decode program."""


def read(run):
    t = run.device_times("decode")
    return 1e3 * sum(t) / len(t) if t else None
