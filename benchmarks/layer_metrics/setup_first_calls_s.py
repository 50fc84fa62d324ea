"""Wall seconds of the watched calls that compiled (`recompile.setup()`:
`sited.first_call_ns`, on the clock every span reads). A wave is traced,
lowered and compiled inside its call; a decode scan is lowered and compiled
inside its call and traced just before it, where the memory ledger
interrogates it. So this, less `setup_lower_s`, `setup_backend_s` and the
waves' part of `setup_trace_s` (the `[setup]` line has it by program), is
what a start spends in its first calls running each program once and moving
its operands. The serve cells (a claimed train step has no watched call);
moves setup_s. A program without the set-up ledger, or one whose watches
compiled nothing, reads nothing.
"""

from benchmarks.lib import setup_readers


def read(obs):
    return setup_readers.sited_seconds(obs, "first_call_ns") or None
