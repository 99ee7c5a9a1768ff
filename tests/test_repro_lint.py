"""Invariant-plane tests: the repro-lint rules against fixture snippets
(positive AND negative per rule family), the disable-comment policy,
the baseline contract, and the gate itself — the full repo lints clean.

Fixtures are source *strings* fed to `lint_source`; `relpath` selects
scoping (determinism rules only fire in DET_CRITICAL modules)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis import lint_paths, lint_source
# rule modules register themselves on import; lint_source doesn't
# auto-import them the way lint_paths does
from repro.analysis import (rules_determinism,  # noqa: F401
                            rules_pallas, rules_rng, rules_threading)

REPO = Path(__file__).resolve().parents[1]
DET_PATH = "src/repro/federated/fixture.py"     # determinism-critical
PLAIN_PATH = "src/repro/fixture.py"             # not det-critical


def lint(src, relpath=PLAIN_PATH, rules=None):
    return lint_source(textwrap.dedent(src), relpath=relpath,
                       rules=rules)


def rule_ids(violations):
    return {v.rule for v in violations}


# ---- RNG discipline ------------------------------------------------------

class TestRngRules:
    def test_bare_numpy_draw_flagged(self):
        vs = lint("""
            import numpy as np
            def sample():
                return np.random.permutation(10)
        """, rules=["rng-bare"])
        assert rule_ids(vs) == {"rng-bare"}

    def test_seeded_stream_clean(self):
        vs = lint("""
            import numpy as np
            def sample(seed):
                rng = np.random.RandomState(seed)
                return rng.permutation(10)
        """, rules=["rng-bare", "rng-unseeded"])
        assert not vs

    def test_stdlib_random_flagged(self):
        assert rule_ids(lint("import random\n")) == {"rng-stdlib"}
        assert rule_ids(lint("from random import shuffle\n")) \
            == {"rng-stdlib"}

    def test_numpy_random_import_not_confused_with_stdlib(self):
        assert not lint("import numpy.random\n", rules=["rng-stdlib"])

    def test_unseeded_constructors_flagged(self):
        vs = lint("""
            import numpy as np
            a = np.random.RandomState()
            b = np.random.default_rng()
        """, rules=["rng-unseeded"])
        assert len(vs) == 2 and rule_ids(vs) == {"rng-unseeded"}

    def test_time_derived_seed_flagged(self):
        vs = lint("""
            import time
            import numpy as np
            rng = np.random.RandomState(int(time.time()))
        """, rules=["rng-time-seed"])
        assert rule_ids(vs) == {"rng-time-seed"}

    def test_seed_assignment_from_wallclock_flagged(self):
        vs = lint("""
            import time
            base_seed = int(time.time_ns())
        """, rules=["rng-time-seed"])
        assert rule_ids(vs) == {"rng-time-seed"}

    def test_explicit_seed_clean(self):
        vs = lint("""
            import numpy as np
            rng = np.random.RandomState(1234)
            gen = np.random.default_rng(np.random.SeedSequence(7))
        """, rules=["rng-bare", "rng-unseeded", "rng-time-seed"])
        assert not vs


# ---- Determinism ---------------------------------------------------------

class TestDeterminismRules:
    def test_wallclock_in_critical_module_flagged(self):
        vs = lint("""
            import time
            def stamp():
                return time.time()
        """, relpath=DET_PATH, rules=["det-wallclock"])
        assert rule_ids(vs) == {"det-wallclock"}

    def test_interval_timers_stay_legal(self):
        vs = lint("""
            import time
            def elapsed(t0):
                return time.perf_counter() - t0
            def deadline():
                return time.monotonic() + 5.0
        """, relpath=DET_PATH, rules=["det-wallclock"])
        assert not vs

    def test_wallclock_outside_critical_scope_ignored(self):
        vs = lint("import time\nt = time.time()\n",
                  relpath="benchmarks/bench_fixture.py",
                  rules=["det-wallclock"])
        assert not vs

    def test_serving_plane_is_det_critical(self):
        # the serving engine (DESIGN.md §18) ships under the
        # src/repro/federated/ DET_CRITICAL prefix — pin that a
        # refactor of the scoping can't silently drop it
        vs = lint("import time\nt = time.time()\n",
                  relpath="src/repro/federated/serving.py",
                  rules=["det-wallclock"])
        assert rule_ids(vs) == {"det-wallclock"}
        assert (REPO / "src/repro/federated/serving.py").exists()

    def test_set_iteration_into_accumulator_flagged(self):
        vs = lint("""
            def total(weights):
                acc = 0.0
                for w in set(weights):
                    acc += w
                return acc
        """, relpath=DET_PATH, rules=["det-unordered-iter"])
        assert rule_ids(vs) == {"det-unordered-iter"}

    def test_sum_over_dict_values_flagged(self):
        vs = lint("""
            def total(per_client):
                return sum(per_client.values())
        """, relpath=DET_PATH, rules=["det-unordered-iter"])
        assert rule_ids(vs) == {"det-unordered-iter"}

    def test_sorted_wrapper_clean(self):
        vs = lint("""
            def total(per_client):
                acc = 0.0
                for k in sorted(per_client.keys()):
                    acc += per_client[k]
                return acc + sum(sorted(per_client.values()))
        """, relpath=DET_PATH, rules=["det-unordered-iter"])
        assert not vs


# ---- Thread safety -------------------------------------------------------

_POOL_FIXTURE = """
    import threading

    class Pool:
        def __init__(self):
            self._lock = threading.Lock()
            self._done = 0
            self._t = threading.Thread(target=self._work)

        def _work(self):
            {write}
"""


class TestThreadRules:
    def test_unguarded_worker_write_flagged(self):
        vs = lint(_POOL_FIXTURE.format(write="self._done = 1"),
                  rules=["thread-unguarded-write"])
        assert rule_ids(vs) == {"thread-unguarded-write"}

    def test_locked_worker_write_clean(self):
        write = "with self._lock:\n                self._done = 1"
        vs = lint(_POOL_FIXTURE.format(write=write),
                  rules=["thread-unguarded-write"])
        assert not vs

    def test_worker_class_without_lock_flagged(self):
        vs = lint("""
            import threading
            class P:
                def start(self):
                    threading.Thread(target=self._run).start()
                def _run(self):
                    self._err = ValueError("x")
        """, rules=["thread-unguarded-write"])
        assert rule_ids(vs) == {"thread-unguarded-write"}
        assert "no lock attribute" in vs[0].message

    def test_init_is_exempt(self):
        vs = lint("""
            import threading
            class P:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0
                    self._t = threading.Thread(target=self._work)
                def _work(self):
                    with self._lock:
                        self._n += 1
        """, rules=["thread-unguarded-write"])
        assert not vs

    def test_blocking_call_under_lock_flagged(self):
        vs = lint("""
            import threading
            class R:
                def __init__(self):
                    self._lock = threading.Lock()
                def get(self, ev):
                    with self._lock:
                        ev.wait()
        """, rules=["thread-lock-order"])
        assert rule_ids(vs) == {"thread-lock-order"}

    def test_nested_foreign_lock_flagged(self):
        vs = lint("""
            import threading
            class R:
                def __init__(self):
                    self._lock = threading.Lock()
                def swap(self, other):
                    with self._lock:
                        with other._lock:
                            pass
        """, rules=["thread-lock-order"])
        assert rule_ids(vs) == {"thread-lock-order"}

    def test_wait_outside_lock_clean(self):
        vs = lint("""
            import threading
            class R:
                def __init__(self):
                    self._lock = threading.Lock()
                def get(self, ev):
                    with self._lock:
                        hit = True
                    ev.wait()
                    return hit
        """, rules=["thread-lock-order"])
        assert not vs


# ---- Pallas contracts ----------------------------------------------------

class TestPallasRules:
    def test_index_map_arity_mismatch_flagged(self):
        vs = lint("""
            from jax.experimental import pallas as pl
            def call(x, k, s):
                return pl.pallas_call(
                    k, grid=(4, 4),
                    in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                    out_shape=s)(x)
        """, rules=["pallas-grid-mismatch"])
        assert rule_ids(vs) == {"pallas-grid-mismatch"}

    def test_block_shape_vs_index_rank_flagged(self):
        vs = lint("""
            from jax.experimental import pallas as pl
            def call(x, k, s):
                return pl.pallas_call(
                    k, grid=(4, 4),
                    in_specs=[pl.BlockSpec((1, 8, 128),
                                           lambda i, j: (i, j))],
                    out_shape=s)(x)
        """, rules=["pallas-grid-mismatch"])
        assert rule_ids(vs) == {"pallas-grid-mismatch"}

    def test_defaulted_closure_params_tolerated(self):
        # the `lambda i, j, G=G:` closure-capture idiom from the
        # attention kernels: extra defaulted params are legal
        vs = lint("""
            from jax.experimental import pallas as pl
            def call(x, k, s, G):
                grid = (4, 4)
                return pl.pallas_call(
                    k, grid=grid,
                    in_specs=[pl.BlockSpec(
                        (8, 128), lambda i, j, G=G: (i * G, j))],
                    out_shape=s)(x)
        """, rules=["pallas-grid-mismatch"])
        assert not vs

    def test_aliased_operand_read_after_call_flagged(self):
        vs = lint("""
            from jax.experimental import pallas as pl
            def step(x, k, s):
                out = pl.pallas_call(
                    k, grid=(1,), input_output_aliases={0: 0},
                    out_shape=s)(x)
                return out + x
        """, rules=["pallas-alias-reuse"])
        assert rule_ids(vs) == {"pallas-alias-reuse"}

    def test_aliased_operand_not_reused_clean(self):
        vs = lint("""
            from jax.experimental import pallas as pl
            def step(x, k, s):
                out = pl.pallas_call(
                    k, grid=(1,), input_output_aliases={0: 0},
                    out_shape=s)(x)
                return out
        """, rules=["pallas-alias-reuse"])
        assert not vs

    def test_missing_ref_oracle_flagged(self, tmp_path):
        pkg = tmp_path / "src" / "repro" / "kernels" / "foo"
        pkg.mkdir(parents=True)
        (pkg / "__init__.py").write_text("")
        (pkg / "ops.py").write_text("def op(x):\n    return x\n")
        report = lint_paths([str(tmp_path)], root=str(tmp_path),
                            rules=["pallas-missing-ref"])
        assert rule_ids(report.violations) == {"pallas-missing-ref"}

    def test_ref_wired_into_ops_clean(self, tmp_path):
        pkg = tmp_path / "src" / "repro" / "kernels" / "foo"
        pkg.mkdir(parents=True)
        (pkg / "__init__.py").write_text("")
        (pkg / "ref.py").write_text("def op_ref(x):\n    return x\n")
        (pkg / "ops.py").write_text(
            "from repro.kernels.foo import ref\n")
        report = lint_paths([str(tmp_path)], root=str(tmp_path),
                            rules=["pallas-missing-ref"])
        assert report.clean


# ---- Disable comments & baseline ----------------------------------------

class TestDisablePolicy:
    def test_reasoned_disable_suppresses(self):
        vs = lint("import random"
                  "  # repro-lint: disable=rng-stdlib (fixture)\n")
        assert not vs

    def test_standalone_disable_covers_next_line(self):
        vs = lint("# repro-lint: disable=rng-stdlib (fixture)\n"
                  "import random\n")
        assert not vs

    def test_bare_disable_is_itself_a_violation(self):
        # string split so this *test file's* physical line doesn't
        # itself match the directive regex when the repo gate runs
        vs = lint("import random  # repro-lint: "
                  "disable=rng-stdlib\n")
        # reasonless disable: flagged AND the rule still fires
        assert rule_ids(vs) == {"lint-bad-disable", "rng-stdlib"}

    def test_disable_scoped_to_named_rule(self):
        vs = lint("""
            import random  # repro-lint: disable=rng-bare (wrong rule)
        """)
        assert rule_ids(vs) == {"rng-stdlib"}


class TestBaselineAndGate:
    def test_baseline_suppresses_only_outside_strict(self, tmp_path):
        src = tmp_path / "mod.py"
        src.write_text("import random\n")
        bl = tmp_path / "bl.json"
        bl.write_text(json.dumps(
            [{"rule": "rng-stdlib", "path": "mod.py", "line": 1}]))
        lax = lint_paths([str(src)], root=str(tmp_path),
                         baseline=str(bl))
        assert lax.clean and lax.baseline_suppressed == 1
        strict = lint_paths([str(src)], root=str(tmp_path),
                            baseline=str(bl), strict=True)
        assert not strict.clean
        assert "lint-baseline-nonempty" in rule_ids(strict.violations)

    def test_syntax_error_reported_not_raised(self, tmp_path):
        src = tmp_path / "broken.py"
        src.write_text("def f(:\n")
        report = lint_paths([str(src)], root=str(tmp_path))
        assert rule_ids(report.violations) == {"lint-parse-error"}

    def test_full_repo_lints_clean_strict(self):
        """THE gate: whole tree, strict mode, shipped (empty) baseline."""
        paths = [str(REPO / p)
                 for p in ("src", "examples", "tests")
                 if (REPO / p).is_dir()]
        report = lint_paths(paths, root=str(REPO),
                            baseline=str(REPO /
                                         ".repro-lint-baseline.json"),
                            strict=True)
        assert report.clean, "\n".join(
            v.format() for v in report.violations)
        assert report.files > 50

    def test_shipped_baseline_is_empty(self):
        entries = json.loads(
            (REPO / ".repro-lint-baseline.json").read_text())
        assert entries == []

    def test_cli_entrypoint(self):
        env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
        proc = subprocess.run(
            [sys.executable, "-m", "repro.analysis.lint", "--strict"],
            cwd=str(REPO), env=env, capture_output=True, text=True)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "0 violation(s)" in proc.stdout
