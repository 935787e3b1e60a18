"""Seconds the program's own calls spent compiling, or loading compiled
executables from the persistent compile cache, counted by the program's
compile counters (``repro.core.obs.compile_counts``: backend compile
events inside its ``sim.*`` host spans).  A replay cell compiles in its
warm-up call, so where the window compiles nothing, all of it is set-up.
Nothing to read from a program without those counters."""


def read(ctx):
    try:
        from repro.core import obs
    except ImportError:
        return None
    return obs.compile_counts()["calls"]["compile_s"]
