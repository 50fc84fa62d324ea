"""`sweep.py` for a configuration whose driver is `serve_qwen3next`:

    python benchmarks/sweep_qwen3next.py --config qwen3-next-80b-serve-32k \
        --mix longchat-poisson-over --rates 1.0,1.5,2.0,3.0,4.0 --seconds 30

`sweep.py` names the `serve` driver and may not be edited; it uses of it
`build_server` and `serve_window`, which `serve_qwen3next` has under the
same names (as `sweep_sarvam.py` and the three before it do for theirs).
Same output, same rule for the sustained rate.
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
        "serve_qwen3next" if name == "serve" else name)
    try:
        return sweep.main(argv)
    finally:
        manifest_lib.driver_module = named


if __name__ == "__main__":
    sys.exit(main())
