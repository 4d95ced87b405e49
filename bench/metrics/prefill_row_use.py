"""Requests admitted over the rows the engine's prefill calls computed
(``stats["prefill_calls"]`` times the batch), in the window."""


def read(run):
    calls = run.counters.get("prefill_calls", 0)
    if not calls:
        return None
    admitted = sum(l.rows for l in run.launches if l.kind == "prefill")
    return 100.0 * admitted / (calls * run.mix.batch)
