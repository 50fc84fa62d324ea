"""Project lint (tools/tfdelint.py) + gate diff logic (tools/lintgate.py):
the repo itself must pass clean, seeded fixtures (unlocked threaded
write, unguarded greedy-path split, unregistered knob) must each be
flagged with an actionable message, and lintgate's check() must fail on
census drift, unknown programs, and project violations.
"""

import importlib.util
import os
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tl():
    return _load("tfdelint")


@pytest.fixture(scope="module")
def lg():
    # lintgate's module-top env setup uses setdefault; everything it
    # wants (JAX_PLATFORMS, XLA_FLAGS) is already pinned by conftest.
    # Pre-set the arm flag to off so importing the gate never arms the
    # in-process hlolint seam for unrelated tests.
    os.environ.setdefault("TFDE_HLOLINT", "0")
    return _load("lintgate")


# -- the repo itself ----------------------------------------------------------
def test_repo_passes_project_lint_clean(tl):
    result = tl.lint_repo()
    assert result["violations"] == []
    # the threaded-class table is live: every entry resolved
    assert set(result["lock_audit"]) == {
        f"{f}::{c}" for f, c in tl.LOCKED_CLASSES}
    assert "TFDE_HLOLINT" in result["knobs_seen"]


# -- rule 1: lock discipline --------------------------------------------------
_BOX = textwrap.dedent("""
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0
            self._items = {}

        def bad_aug(self):
            self._n += 1                      # line 11: unlocked RMW

        def bad_publish(self, k, v):
            self._items[k] = v                # line 14: unlocked publish

        def good(self, k, v):
            with self._lock:
                self._n += 1
                self._items[k] = v

        def local_object_ok(self):
            obj = object.__new__(Box)
            obj.fresh = 1                     # local publish: legal
            return obj

        def closure_bad(self):
            with self._lock:
                def cb():
                    self._n = 5               # closure outlives the lock
                return cb
""")


def _write_pkg(tmp_path, rel, src):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(src)
    return str(tmp_path)


def test_unlocked_write_fixture_is_flagged(tl, tmp_path):
    root = _write_pkg(tmp_path, "pkg/box.py", _BOX)
    table = {("pkg/box.py", "Box"): tl.LockSpec(lock="_lock")}
    violations, audit = tl.lint_locks(root, table=table)
    assert audit["pkg/box.py::Box"] == "checked"
    lines = sorted(int(v.split(":")[1]) for v in violations)
    assert len(violations) == 3, violations
    # the aug-assign, the subscript publish, and the closure write — and
    # nothing from good()/local_object_ok()/__init__
    for v in violations:
        assert "with self._lock" in v
    assert any("augmented write to ._n" in v for v in violations)
    assert any("write to self._items" in v for v in violations)
    assert lines[-1] > lines[0]


def test_exempt_attrs_and_external_lock(tl, tmp_path):
    root = _write_pkg(tmp_path, "pkg/box.py", _BOX)
    # exempting the attrs silences exactly those findings
    table = {("pkg/box.py", "Box"): tl.LockSpec(
        lock="_lock", exempt_attrs=("_n", "_items"))}
    violations, _ = tl.lint_locks(root, table=table)
    assert violations == []
    # an external-lock declaration skips the class with its reason
    table = {("pkg/box.py", "Box"): tl.LockSpec(
        external="owner holds the lock")}
    violations, audit = tl.lint_locks(root, table=table)
    assert violations == []
    assert "owner holds the lock" in audit["pkg/box.py::Box"]


def test_stale_locked_classes_table_is_loud(tl, tmp_path):
    root = _write_pkg(tmp_path, "pkg/box.py", _BOX)
    table = {("pkg/box.py", "Vanished"): tl.LockSpec()}
    violations, _ = tl.lint_locks(root, table=table)
    assert len(violations) == 1 and "stale" in violations[0]


def test_lock_rule_catches_the_pr10_aggregate_bug(tl, tmp_path):
    """The exact shape fixed in this PR: ClusterAggregator.rollup()
    mutated `self._known_stale &= ...` and `self._flagged_straggler = ...`
    outside the lock while handler threads read them."""
    src = textwrap.dedent("""
        import threading

        class Agg:
            def __init__(self):
                self._lock = threading.Lock()
                self._known_stale = set()
                self._flagged_straggler = None

            def rollup(self, stale, straggler):
                self._known_stale &= set(stale)
                if straggler >= 0:
                    self._flagged_straggler = straggler
    """)
    root = _write_pkg(tmp_path, "pkg/agg.py", src)
    violations, _ = tl.lint_locks(
        root, table={("pkg/agg.py", "Agg"): tl.LockSpec(lock="_lock")})
    assert len(violations) == 2
    assert any("_known_stale" in v for v in violations)
    assert any("_flagged_straggler" in v for v in violations)


# -- rule 2: greedy-path split ban --------------------------------------------
def test_greedy_split_fixture(tl, tmp_path):
    src = textwrap.dedent("""
        import jax

        def bad(key):
            return jax.random.split(key)          # unguarded

        def guarded(key, temperature):
            if temperature > 0.0:
                return jax.random.split(key)      # sampling branch: ok
            return key

        def else_branch(key, greedy):
            if greedy:
                return key
            else:
                return jax.random.split(key)      # other side: still ok

        def _round_sampled(key):
            return jax.random.split(key)          # sampled-only program: ok
    """)
    root = _write_pkg(tmp_path, "pkg/dec.py", src)
    violations = tl.lint_greedy_split(root, dirs=("pkg",))
    assert len(violations) == 1, violations
    assert "pkg/dec.py:5" in violations[0]
    assert "temperature/greedy" in violations[0]


def test_repo_inference_tree_passes_greedy_split(tl):
    assert tl.lint_greedy_split(ROOT) == []


# -- rule 3: knob audit -------------------------------------------------------
def test_unregistered_knob_fixture(tl, tmp_path):
    src = 'import os\nX = os.environ.get("TFDE_NOT_A_KNOB")\n' \
          'Y = os.environ.get("TFDE_TRACE")\n' \
          'Z = os.environ.get("TFDE_RETRY_MAX_ATTEMPTS")\n'
    root = _write_pkg(tmp_path, "tfde_tpu/mod.py", src)
    violations, seen = tl.lint_knobs(root)
    assert seen == ["TFDE_NOT_A_KNOB", "TFDE_RETRY_MAX_ATTEMPTS",
                    "TFDE_TRACE"]
    # registered name and registered prefix family pass; the stray fails
    # with a pointer at the registry
    assert len(violations) == 1, violations
    assert "TFDE_NOT_A_KNOB" in violations[0]
    assert "tfde_tpu/knobs.py" in violations[0]


def test_unread_knob_fixture(tl):
    """The reverse: a registered knob no literal reads is a violation. A
    member is read through its family's prefix, a family through any
    member."""
    flagged = tl.lint_unread_knobs(
        ["TFDE_TRACE", "TFDE_RETRY_", "TFDE_ADMIT_MAX_QUEUE"])
    named = {v.split("'")[1] for v in flagged}
    assert all("is registered but no file" in v for v in flagged)
    assert "TFDE_FLASH" in named and "TFDE_SLO_" in named
    assert not named & {"TFDE_TRACE", "TFDE_RETRY_", "TFDE_RETRY_DEADLINE",
                        "TFDE_ADMIT_", "TFDE_ADMIT_MAX_QUEUE"}


# -- lintgate diff logic ------------------------------------------------------
def _census(**over):
    c = {"all_reduce": 2, "reduce_scatter": 1, "all_gather": 2,
         "collective_permute": 0, "callbacks": 0, "aliased_outputs": 13,
         "f64_tensors": 0, "bf16_to_f32_converts": 0,
         "collective_bytes": {"all_reduce": 9560}, "large_constants": []}
    c.update(over)
    return c


def _obs(census=None, violations=(), project_violations=(),
         knobs=("TFDE_TRACE",), name="train_step/int8+replicated"):
    return {
        "programs": {name: {"census": census or _census(),
                            "violations": list(violations)}},
        "project": {"violations": list(project_violations),
                    "lock_audit": {"a.py::A": "checked"},
                    "knobs_seen": list(knobs)},
    }


def test_lintgate_check_clean(lg):
    base = _obs()
    assert lg.check(_obs(), base) == []


def test_lintgate_check_fails_on_extra_collective(lg):
    base = _obs()
    fails = lg.check(_obs(census=_census(all_reduce=3)), base)
    assert len(fails) == 1
    assert "all_reduce 3 != baseline 2" in fails[0]
    assert "--update" in fails[0]  # actionable: names the re-baseline cmd


def test_lintgate_check_fails_on_payload_drift(lg):
    base = _obs()
    drifted = _census(collective_bytes={"all_reduce": 99999})
    fails = lg.check(_obs(census=drifted), base)
    assert len(fails) == 1 and "payload bytes" in fails[0]


def test_lintgate_check_fails_on_violation_and_unknown_names(lg):
    base = _obs()
    fails = lg.check(_obs(violations=["p: stray host callback"]), base)
    assert any("violation: p: stray host callback" in f for f in fails)
    # a program the baseline has never seen
    fails = lg.check(_obs(name="serve/decode/k9"), base)
    assert any("not in baseline" in f for f in fails)
    # a baseline program the workload lost
    lost = _obs()
    lost["programs"] = {}
    fails = lg.check(lost, base)
    assert any("not observed" in f for f in fails)


def test_lintgate_check_fails_on_project_drift(lg):
    base = _obs()
    fails = lg.check(_obs(project_violations=["x.py:3: unlocked write"]),
                     base)
    assert any("unlocked write" in f for f in fails)
    fails = lg.check(_obs(knobs=("TFDE_TRACE", "TFDE_NEW")), base)
    assert any("knob census changed" in f for f in fails)


def test_lintgate_baseline_is_committed_and_covers_the_matrix(lg):
    import json

    with open(os.path.join(ROOT, "tools", "lintgate_baseline.json")) as f:
        base = json.load(f)
    names = set(base["programs"])
    # all four transport x sharding combos
    for t, s in lg.TRAIN_COMBOS:
        assert f"train_step/{t}+{s}" in names
    # decode scan + all three prefill admission kinds
    assert any(n.startswith("serve/decode/") for n in names)
    assert any(n.startswith("serve/prefill/") for n in names)
    assert any(n.startswith("serve/prefill_warm/") for n in names)
    assert any(n.startswith("serve/prefill_primed/") for n in names)
    # the baseline itself is violation-free
    for prog in base["programs"].values():
        assert prog["violations"] == []
    assert base["project"]["violations"] == []


def test_guarded_attrs_flag_unlocked_reads(tl, tmp_path):
    """The PR-14 regression shape: router.load() read `self.batcher._queue`
    without the replica lock. `guarded_attrs` makes the lock rule flag ANY
    access — reads included — to the named attributes outside the lock."""
    src = textwrap.dedent("""
        import threading

        class Rep:
            def __init__(self):
                self._lock = threading.Lock()
                self.batcher = object()       # __init__ is exempt

            def bad_read(self):
                return len(self.batcher._queue)   # unlocked read

            def bad_alias(self):
                b = self.batcher                  # unlocked alias grab
                return b

            def good(self):
                with self._lock:
                    return len(self.batcher._queue)
    """)
    root = _write_pkg(tmp_path, "pkg/rep.py", src)
    table = {("pkg/rep.py", "Rep"): tl.LockSpec(
        lock="_lock", guarded_attrs=("batcher",))}
    violations, audit = tl.lint_locks(root, table=table)
    assert audit["pkg/rep.py::Rep"] == "checked"
    assert len(violations) == 2, violations
    for v in violations:
        assert "access to self.batcher" in v
    # without the guard, plain reads stay legal (writes-only rule)
    table = {("pkg/rep.py", "Rep"): tl.LockSpec(lock="_lock")}
    violations, _ = tl.lint_locks(root, table=table)
    assert violations == []
