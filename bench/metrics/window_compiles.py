"""Executables the engine built while serving the window
(``stats["compiles"]``): each one stalls every slot."""


def read(run):
    return run.counters.get("compiles")
