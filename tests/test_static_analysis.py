"""Tests for the repro-lint static-analysis pass (src/repro/analysis/).

Each checker gets a positive fixture (a violation it must flag — a
checker that cannot fail proves nothing) and a negative fixture
(compliant code it must stay silent on).  The baseline file round-trips
and the split logic implements the empty-delta gate.
"""
import ast
import textwrap

from repro.analysis import base
from repro.analysis import catalog as cat
from repro.analysis import dtypes
from repro.analysis import jit_boundary as jb
from repro.analysis import locks


# ------------------------------------------------------------ base/baseline

def test_finding_render_and_fingerprint_stability():
    f = base.Finding(file="src/a.py", line=42, rule="dtype-discipline",
                     message="affine in bf16 at row 17", symbol="deq")
    assert f.render() == "src/a.py:42 dtype-discipline affine in bf16 at row 17"
    g = base.Finding(file="src/a.py", line=99, rule="dtype-discipline",
                     message="affine in bf16 at row 23", symbol="deq")
    # fingerprints ignore line numbers and collapse digits: moving code or
    # renumbering rows must not invalidate a reviewed suppression
    assert f.fingerprint() == g.fingerprint()
    h = base.Finding(file="src/b.py", line=42, rule="dtype-discipline",
                     message="affine in bf16 at row 17", symbol="deq")
    assert f.fingerprint() != h.fingerprint()


def test_baseline_roundtrip_and_split(tmp_path):
    f1 = base.Finding(file="a.py", line=1, rule="r", message="one")
    f2 = base.Finding(file="b.py", line=2, rule="r", message="two")
    b = base.Baseline()
    b.absorb([f1])
    path = tmp_path / "baseline.json"
    b.save(path)
    b2 = base.Baseline.load(path)
    assert b2.entries.keys() == b.entries.keys()
    new, accepted, stale = b2.split([f1, f2])
    assert [x.message for x in new] == ["two"]
    assert [x.message for x in accepted] == ["one"]
    assert stale == []
    # stale: baseline entry matching nothing current
    new, accepted, stale = b2.split([f2])
    assert stale == [f1.fingerprint()]


def test_baseline_missing_file_is_empty(tmp_path):
    b = base.Baseline.load(tmp_path / "nope.json")
    assert b.entries == {}


def test_checker_registry_resolves():
    for name in base.CHECKERS:
        assert callable(base.resolve(name))


# -------------------------------------------------------------------- dtype

def test_dtype_checker_flags_bf16_affine():
    src = textwrap.dedent("""
        def deq(q, scale, zero):
            qf = q.astype(jnp.bfloat16)
            return qf * scale + zero
    """)
    got = dtypes.check_source(src, "fix.py")
    assert len(got) == 1 and got[0].rule == "dtype-discipline"
    assert "bfloat16" in got[0].message


def test_dtype_checker_flags_dynamic_dtype_affine():
    src = textwrap.dedent("""
        def deq(q, scale, zero, x):
            dt = x.dtype
            qf = q.astype(dt)
            return qf * scale + zero
    """)
    got = dtypes.check_source(src, "fix.py")
    assert len(got) == 1 and "dynamic" in got[0].message


def test_dtype_checker_silent_on_f32_affine():
    src = textwrap.dedent("""
        def deq(q, scale, zero):
            qf = q.astype(jnp.float32)
            out = qf * scale.astype(jnp.float32) + zero.astype(jnp.float32)
            return out.astype(jnp.bfloat16)   # cast AFTER the affine is fine
    """)
    assert dtypes.check_source(src, "fix.py") == []


def test_dtype_checker_silent_on_unresolvable():
    # unknown factor dtypes are not guessed at — no finding
    src = "def f(a, b, c):\n    return a * b + c\n"
    assert dtypes.check_source(src, "fix.py") == []


# ------------------------------------------------------------- jit boundary

def test_jit_boundary_flags_obs_in_scan_body():
    src = textwrap.dedent("""
        def body(carry, xs):
            obs_metrics.counter("steps").inc()
            return carry, xs

        def run(xs):
            return jax.lax.scan(body, 0, xs)
    """)
    got = jb.check_source(src, "fix.py")
    assert len(got) == 1 and got[0].symbol == "body"
    assert "obs_metrics.counter" in got[0].message


def test_jit_boundary_follows_partial_alias_into_pallas():
    src = textwrap.dedent("""
        def _kern(x_ref, o_ref):
            print("traced!")

        def launch(x):
            kernel = functools.partial(_kern)
            return pl.pallas_call(kernel, out_shape=x)(x)
    """)
    got = jb.check_source(src, "fix.py")
    assert [f.symbol for f in got] == ["_kern"]


def test_jit_boundary_silent_outside_staging():
    src = textwrap.dedent("""
        def host_loop(xs):
            obs_metrics.counter("calls").inc()
            print("fine here")
            return [x + 1 for x in xs]
    """)
    assert jb.check_source(src, "fix.py") == []


def test_jit_boundary_exempts_jax_debug():
    src = textwrap.dedent("""
        @jax.jit
        def f(x):
            jax.debug.print("x={}", x)
            return x + 1
    """)
    assert jb.check_source(src, "fix.py") == []


# -------------------------------------------------------------------- locks

_LOCK_POLICY = locks.LockPolicy(
    lock="_lock", guarded=frozenset({"counter"}),
    single_writer={"solo": "single writer by contract"})


def _lock_findings(src):
    cls = next(n for n in ast.walk(ast.parse(textwrap.dedent(src)))
               if isinstance(n, ast.ClassDef))
    return locks.check_class(cls, _LOCK_POLICY, "fix.py")


def test_lock_checker_flags_unguarded_write():
    got = _lock_findings("""
        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self.counter = 0
            def bump(self):
                self.counter += 1
    """)
    assert len(got) == 1 and "outside" in got[0].message
    assert got[0].symbol == "C.bump"


def test_lock_checker_accepts_locked_write_and_single_writer():
    got = _lock_findings("""
        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self.counter = 0
                self.solo = []
            def bump(self):
                with self._lock:
                    self.counter += 1
                self.solo.append(1)
    """)
    assert got == []


def test_lock_checker_flags_undeclared_attribute():
    got = _lock_findings("""
        class C:
            def __init__(self):
                self._lock = threading.Lock()
            def sneak(self):
                self.rogue = 1
    """)
    assert len(got) == 1 and "undeclared" in got[0].message


def test_lock_checker_flags_missing_lock():
    got = _lock_findings("""
        class C:
            def __init__(self):
                self.counter = 0
    """)
    assert len(got) == 1 and "never assigned" in got[0].message


def test_lock_checker_mutating_call_counts_as_write():
    got = _lock_findings("""
        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self.counter = {}
            def bump(self, k):
                self.counter.update({k: 1})
    """)
    assert len(got) == 1 and got[0].symbol == "C.bump"


def test_lock_policies_match_repo():
    assert locks.check(base.REPO_ROOT) == []


# ------------------------------------------------------------- catalog sync

def test_catalog_collect_emits_and_dynamic_name(tmp_path):
    pkg = tmp_path / "src" / "repro" / "serving"
    pkg.mkdir(parents=True)
    (pkg / "mod.py").write_text(textwrap.dedent("""
        def f(name):
            obs_trace.span("serve.step")
            obs_metrics.counter("queue.shed").inc()
            obs_metrics.counter(name).inc()
    """))
    sites, findings = cat.collect_emits(tmp_path)
    assert ("spans", "serve.step") in sites
    assert ("metrics", "queue.shed") in sites
    assert len(findings) == 1 and "non-literal" in findings[0].message


def test_catalog_sync_clean_on_repo():
    assert cat.check(base.REPO_ROOT) == []
