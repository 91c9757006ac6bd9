"""Set-up spent tracing, lowering and compiling programs, or loading them
from the persistent cache: the host seconds, from the start of the process
to the window's opening, inside the intervals the program's compile
counter (``repro.obs.compile_counter``) recorded.  The number of compiles
that ended inside the window is logged on standard error; it should be 0.
None where the program has no such counter."""
import sys

NAME, UNIT = "compile_s", "s"
LAYER, MOVES = "compile", "setup_s"


def read(run):
    try:
        from repro.obs.telemetry import compile_counter
    except ImportError:
        return None
    counter = compile_counter()
    if not counter.intervals():
        return None
    lo, hi = run.window
    print(f"[bench] compiles that ended inside the window: "
          f"{counter.compiles_between(lo, hi)}", file=sys.stderr, flush=True)
    return counter.busy_s(until=lo)
