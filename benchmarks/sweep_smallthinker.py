"""`sweep.py` for a configuration whose driver is `serve_smallthinker`:

    python benchmarks/sweep_smallthinker.py --config smallthinker-21b-serve-16k \
        --mix mixed-poisson-over --rates 1.5,2,2.5,3,4 --seconds 25

`sweep.py` names the `serve` driver and may not be edited; it uses of it
`build_server` and `serve_window`, which `serve_smallthinker` has under the
same names (as `sweep_evabyte.py` and `sweep_granite.py` do for theirs). Same output, same
rule for the sustained rate.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    from benchmarks import sweep
    from benchmarks.lib import manifest as manifest_lib

    named = manifest_lib.driver_module
    manifest_lib.driver_module = lambda name: named(
        "serve_smallthinker" if name == "serve" else name)
    try:
        return sweep.main(argv)
    finally:
        manifest_lib.driver_module = named


if __name__ == "__main__":
    sys.exit(main())
