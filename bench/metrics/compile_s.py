"""compile_s: seconds the process spent tracing, lowering and compiling
(or loading from the persistent cache) its programs, from the program's
own counter (repro.obs.compiles) read when the traced window has closed;
a steady mix compiles nothing in the window, so this is set-up's."""
from bench import scopes


def read(ctx):
    snap = scopes.compile_counter(ctx)
    return None if snap is None else snap["total_s"]
