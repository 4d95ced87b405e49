"""Rows admitted over the rows the engine's prefill calls computed
(``stats["rows_admitted"]`` over ``stats["rows_prefilled"]``), in the
window: the share of prefilled rows that held a request."""


def read(run):
    prefilled = run.counters.get("rows_prefilled", 0)
    if not prefilled:
        return None
    return 100.0 * run.counters["rows_admitted"] / prefilled
