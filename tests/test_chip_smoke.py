"""The bring-up contract that can be checked without a chip: where the
compile cache goes, and that chip_smoke.py refuses anything but a TPU."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRINT_CACHE_DIR = (
    "import json, jax; before = jax.config.jax_compilation_cache_dir; "
    "import tfde_tpu; "
    "print(json.dumps([before, jax.config.jax_compilation_cache_dir]))"
)


def _fresh_interpreter(code_or_script, env_overrides, cwd=ROOT):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT, **env_overrides)
    argv = ([sys.executable, code_or_script]
            if code_or_script.endswith(".py")
            else [sys.executable, "-c", code_or_script])
    return subprocess.run(argv, env=env, cwd=cwd, capture_output=True,
                          text=True, timeout=120)


def test_compile_cache_env_var_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself and the package
    sets no other directory."""
    placed = str(tmp_path / "placed")
    proc = _fresh_interpreter(_PRINT_CACHE_DIR,
                              {"JAX_COMPILATION_CACHE_DIR": placed})
    assert proc.returncode == 0, proc.stderr
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == [placed, placed]


def test_compile_cache_default_is_fixed_under_the_checkout(tmp_path):
    """Unset: <checkout>/.jax_cache, derived from the package's location —
    the same from any working directory, in any fresh interpreter (the
    directory is part of the cache key, so a path that moves never hits)."""
    seen = []
    for cwd in (ROOT, str(tmp_path)):
        proc = _fresh_interpreter(_PRINT_CACHE_DIR, {}, cwd=cwd)
        assert proc.returncode == 0, proc.stderr
        seen.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    expected = os.path.join(ROOT, ".jax_cache")
    assert seen == [[None, expected], [None, expected]]


def test_chip_smoke_refuses_cpu():
    """No TPU: non-zero exit, the platform it found named on stderr, and no
    result on stdout — never a CPU fallback."""
    proc = _fresh_interpreter(os.path.join(ROOT, "chip_smoke.py"), {})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "platform='cpu'" in proc.stderr


def test_chip_smoke_result_line_has_exactly_the_agreed_keys():
    """The last line of stdout is parsed by whoever runs the check: `ok`
    and `device` {platform, kind, count} and nothing else (the run's other
    facts go on the `[summary]` line before it)."""
    import jax

    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    devices = jax.devices()
    for ok in (True, False):
        line = chip_smoke.result_line(ok, devices)
        assert "\n" not in line
        assert json.loads(line) == {"ok": ok, "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}}
