"""Start-up layer: trace + lower + backend seconds of the programs
``donated_step`` built (``hvdt_compile_seconds_total{role="step"}``): what
a user's restart pays; the rest of ``setup_trace_s``, ``setup_lower_s`` and
``setup_cache_load_s`` is the yardstick's own check.  Moves ``setup_s``.

A reader of the start-up layer returns the process's total when it is
called.  That is set-up's total: nothing may compile inside the window
(``correct`` demands ``compiles_in_window == 0``) and the ahead-of-time
executable the harness calls traces nothing.  None where the program has
no compile ledger (a parent older than PR 51)."""

from benchmark.layer_metrics.setup_ledger import process_ledger


def read(ctx):
    ledger = process_ledger()
    return None if ledger is None else ledger.seconds(role="step")
