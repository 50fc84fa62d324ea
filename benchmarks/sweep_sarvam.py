"""`sweep.py` for a configuration whose driver is `serve_sarvam`:

    python benchmarks/sweep_sarvam.py --config sarvam-105b-serve-32k \
        --mix longctx-poisson-over --rates 1.0,1.4,1.8,2.2,2.8 --seconds 30

`sweep.py` names the `serve` driver and may not be edited; it uses of it
`build_server` and `serve_window`, which `serve_sarvam` has under the
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
        "serve_sarvam" if name == "serve" else name)
    try:
        return sweep.main(argv)
    finally:
        manifest_lib.driver_module = named


if __name__ == "__main__":
    sys.exit(main())
