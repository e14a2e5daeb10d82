"""repro.analysis — the program auditor and thread lint.

Fast legs run in-process (jaxpr tracing only, single device — a
1-device mesh still produces the shard_map primitive, which is all the
R1 walker needs).  The two CLI legs run the REAL auditor end-to-end in
subprocesses with 4 virtual devices, exactly as the static-audit CI
job does: exit 0 on the committed baseline, non-zero against an empty
one (the accepted R1 findings become "new").
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ,
           XLA_FLAGS="--xla_force_host_platform_device_count=4",
           PYTHONPATH=f"{REPO}/src:{REPO}")


# ---------------------------------------------------------------------------
# R1 — the PR-5 regression fixture
# ---------------------------------------------------------------------------

def _while_under_shard_map(step_fn):
    """A shard_map program with a data-dependent while whose body runs
    `step_fn` — the exact shape of the PR-5 deadlock when `step_fn`
    sorts."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    d = jax.device_count()
    mesh = jax.make_mesh((d,), ("x",))

    def local(x):
        def cond(c):
            i, v = c
            return jnp.logical_and(i < 8, jnp.min(v) > -1e6)

        def step(c):
            i, v = c
            return i + 1, step_fn(v)

        return jax.lax.while_loop(cond, step, (0, x))[1]

    f = jax.shard_map(local, mesh=mesh, in_specs=(P("x"),),
                      out_specs=P("x"), check_vma=False)
    return jax.make_jaxpr(f)(jnp.ones((d * 8,), jnp.float32))


def test_r1_flags_pr5_sort_in_while_fixture():
    """argsort inside a data-dependent while under shard_map is the
    PR-5 deadlock class — R1 must flag it."""
    import jax.numpy as jnp

    from repro.analysis.jaxpr_walk import collectives_in_dynamic_loop

    jaxpr = _while_under_shard_map(
        lambda v: v[jnp.argsort(v)] * 0.9)
    codes = {f.code for f in
             collectives_in_dynamic_loop(jaxpr, "fixture")}
    assert "sort-in-while-under-shard_map" in codes, codes


def test_r1_top_k_in_while_is_exempt():
    """top_k lowers to a fixed-size shard-local reduction — the scan
    cores depend on it inside the while body, so R1 must not fire."""
    import jax
    import jax.numpy as jnp

    from repro.analysis.jaxpr_walk import collectives_in_dynamic_loop

    def step(v):
        top, _ = jax.lax.top_k(v, v.shape[0])
        return top * 0.9 + jnp.min(v) * 0.0

    jaxpr = _while_under_shard_map(step)
    assert collectives_in_dynamic_loop(jaxpr, "fixture") == []


def test_r1_sort_in_plain_while_lower_severity_code():
    """Outside shard_map the same shape gets the advisory code — the
    PR-5 bug entered exactly by wrapping such a program later."""
    import jax
    import jax.numpy as jnp

    from repro.analysis.jaxpr_walk import collectives_in_dynamic_loop

    def f(x):
        def step(c):
            i, v = c
            return i + 1, v[jnp.argsort(v)]

        return jax.lax.while_loop(lambda c: c[0] < 4, step, (0, x))[1]

    jaxpr = jax.make_jaxpr(f)(jnp.ones((8,), jnp.float32))
    codes = {f.code for f in
             collectives_in_dynamic_loop(jaxpr, "fixture")}
    assert codes == {"sort-in-while"}, codes


def test_r1_real_scan_cores_audit_clean():
    """The shipped device scan programs must stay free of R1 findings
    — `executor._survivors_first` (mask-cumsum pack) exists precisely
    so no sort runs inside the scan while body.  This is the regression
    pin for the PR-5 bug class."""
    from repro.analysis.jaxpr_walk import collectives_in_dynamic_loop

    local = _tiny_local_engine()
    for rec in local.audit_programs():
        findings = collectives_in_dynamic_loop(rec["jaxpr"], rec["name"])
        assert findings == [], (rec["name"],
                                [f.code for f in findings])


# ---------------------------------------------------------------------------
# R3 — silent f64 downcast (forward taint)
# ---------------------------------------------------------------------------

def test_r3_flags_tainted_downcast_and_spares_untainted():
    import jax
    import jax.numpy as jnp

    from repro.analysis.jaxpr_walk import f64_downcasts

    with jax.enable_x64(True):
        def bad(hi, lo):
            return ((hi + lo).astype(jnp.float32) * 2.0)

        def ok(hi, other):
            # downcast happens, but NOT on the tainted operand
            return hi.sum(), other.astype(jnp.float32)

        z = jnp.zeros((4,), jnp.float64)
        bad_jaxpr = jax.make_jaxpr(bad)(z, z)
        ok_jaxpr = jax.make_jaxpr(ok)(z, z)

    hits = f64_downcasts(bad_jaxpr, "fixture", taint_invars=(0, 1))
    assert any(f.code == "f64-downcast-float32" for f in hits), hits
    assert f64_downcasts(ok_jaxpr, "fixture", taint_invars=(0,)) == []


# ---------------------------------------------------------------------------
# R1 over HLO text — the compiler-inserted variant
# ---------------------------------------------------------------------------

_HLO_FIXTURE = textwrap.dedent("""\
    HloModule fixture

    %add (a: f32[], b: f32[]) -> f32[] {
      ROOT %s = f32[] add(f32[] %a, f32[] %b)
    }

    %body.7 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
      %v = f32[8] get-tuple-element((s32[], f32[8]) %p), index=1
      %ar = f32[8] all-reduce(f32[8] %v), to_apply=%add
      ROOT %t = (s32[], f32[8]) tuple(s32[] %i, f32[8] %ar)
    }

    %cond.7 (p: (s32[], f32[8])) -> pred[] {
      ROOT %lt = pred[] compare(s32[] %i, s32[] %n), direction=LT
    }

    ENTRY %main (x: f32[8]) -> f32[8] {
      %init = (s32[], f32[8]) tuple(s32[] %c0, f32[8] %x)
      %w = (s32[], f32[8]) while((s32[], f32[8]) %init), \
condition=%cond.7, body=%body.7
      ROOT %out = f32[8] get-tuple-element((s32[], f32[8]) %w), index=1
    }
    """)


def test_hlo_while_collective_parser():
    from repro.analysis.jaxpr_walk import hlo_while_collectives

    hits = hlo_while_collectives(_HLO_FIXTURE, "fixture")
    assert {f.code for f in hits} == {"hlo-all-reduce-in-while"}, hits
    clean = _HLO_FIXTURE.replace(
        "%ar = f32[8] all-reduce(f32[8] %v), to_apply=%add",
        "%ar = f32[8] negate(f32[8] %v)")
    assert hlo_while_collectives(clean, "fixture") == []


# ---------------------------------------------------------------------------
# R2 — host-sync budget
# ---------------------------------------------------------------------------

def _tiny_local_engine(max_batch: int = 8):
    from repro.core import Collection, EnvelopeParams, UlisseEngine

    rng = np.random.default_rng(0)
    data = np.cumsum(rng.normal(size=(4, 96)), -1).astype(np.float32)
    p = EnvelopeParams(lmin=32, lmax=48, gamma=4, seg_len=8, card=64)
    return UlisseEngine.from_collection(Collection.from_array(data), p,
                                        max_batch=max_batch)


def test_transfer_counter_counts_real_traffic():
    """The counter must see what actually crosses: one device_get on a
    pytree is ONE sync (internal per-leaf materialization is the same
    transfer), while N separate np.asarray exports are N."""
    import jax
    import jax.numpy as jnp

    from repro.analysis.transfers import count_transfers

    arrs = tuple(jnp.arange(4.0) + i for i in range(3))
    with count_transfers() as c:
        jax.device_get(arrs)
    assert (c.device_gets, c.array_exports) == (1, 0), vars(c)
    with count_transfers() as c:
        for a in arrs:                      # deliberately chatty
            np.asarray(a)
    assert (c.device_gets, c.array_exports) == (0, 3), vars(c)


@pytest.mark.parametrize("batch", [1, 8])
def test_device_paths_hold_host_sync_budget(batch):
    """Exact, approx, and range device paths: at most ONE device_get
    and ZERO stray numpy exports per steady-state batch — the §8–§10
    single-sync promise, now pinned at B=1 and B=8."""
    from repro.analysis.transfers import measure_steady_state
    from repro.core import QuerySpec

    eng = _tiny_local_engine(max_batch=8)
    q = np.sin(np.linspace(0.0, 6.0, 32)).astype(np.float32)
    specs = {
        "exact": QuerySpec(k=3, chunk_size=16),
        "approx": QuerySpec(k=3, mode="approx", chunk_size=16),
        "range": QuerySpec(eps=0.5, range_capacity=64, chunk_size=16),
    }
    for name, spec in specs.items():
        gets, exports = measure_steady_state(
            lambda spec=spec: eng.search([q] * batch, spec))
        assert gets <= 1 and exports == 0, (name, batch, gets, exports)


def test_host_backend_is_the_chatty_reference():
    """The legacy host backend crosses the device boundary per chunk,
    not per batch — it must register MORE than one transfer per query,
    which validates that the zeros on the device paths above are a
    measured property, not a dead counter."""
    from repro.analysis.transfers import measure_steady_state
    from repro.core import QuerySpec

    eng = _tiny_local_engine()
    q = np.sin(np.linspace(0.0, 6.0, 32)).astype(np.float32)
    spec = QuerySpec(k=3, chunk_size=16, scan_backend="host",
                     verify_top=4)
    gets, exports = measure_steady_state(lambda: eng.search([q], spec))
    assert gets + exports > 1, (gets, exports)


# ---------------------------------------------------------------------------
# R4 / R5 — declared keys and shared constants
# ---------------------------------------------------------------------------

def test_r4_clean_on_shipped_keys_and_catches_dropped_field():
    from repro.analysis import audit
    from repro.core import engine as eng

    assert audit._audit_retrace_keys() == []
    # drop k from the sharded knn key: R4 must notice
    orig = eng.PROGRAM_KEY_SPECS["sharded_knn"]
    try:
        eng.PROGRAM_KEY_SPECS["sharded_knn"] = {
            "key": lambda s: ("knn", s.measure, s.r),
            "not_in_key": orig["not_in_key"],
        }
        codes = {f.code for f in audit._audit_retrace_keys()}
        assert "unhashed-field-k" in codes, codes
    finally:
        eng.PROGRAM_KEY_SPECS["sharded_knn"] = orig


def test_r5_clean_and_catches_width_drift(monkeypatch):
    from repro.analysis import audit
    from repro.core import executor

    assert audit._audit_constants([]) == []
    monkeypatch.setattr(executor, "STATS_WIDTH",
                        executor.STATS_WIDTH + 1)
    codes = {f.code for f in audit._audit_constants([])}
    assert "stats-width-drift" in codes, codes


def test_obs_schema_derives_from_executor():
    """repro.obs must consume executor.STATS_COLUMNS, not restate it —
    the import-time check trips if the exporter drops a device stats
    column."""
    import repro.obs as obs
    from repro.core import executor

    obs._check_stats_schema()               # shipped state: passes
    exported = {f for f, _ in obs._STATS_COUNTERS}
    assert set(executor.STATS_COLUMNS) <= exported


# ---------------------------------------------------------------------------
# R6 — module reachability
# ---------------------------------------------------------------------------

def test_r6_flags_orphan_and_keeps_test_reachable(tmp_path):
    from repro.analysis.deadcode import audit_deadcode

    src = tmp_path / "src" / "repro"
    src.mkdir(parents=True)
    (src / "__init__.py").write_text("from repro import used\n")
    (src / "used.py").write_text("X = 1\n")
    (src / "orphan.py").write_text("Y = 2\n")
    (src / "testonly.py").write_text("Z = 3\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_t.py").write_text("import repro.testonly\n")

    subjects = {f.subject for f in audit_deadcode(str(tmp_path))}
    assert subjects == {"repro.orphan"}, subjects


def test_r6_shipped_tree_has_no_dead_modules():
    from repro.analysis.deadcode import audit_deadcode

    assert audit_deadcode(REPO) == []


# ---------------------------------------------------------------------------
# T1 — thread-discipline lint
# ---------------------------------------------------------------------------

def test_thread_lint_clean_on_shipped_serve():
    from repro.analysis.threads import lint_serve

    assert lint_serve(REPO) == []


def test_thread_lint_catches_injected_cross_thread_write():
    """close() runs on the client thread; `_version` is
    dispatcher-owned.  Injecting the write must produce a
    cross-thread-write finding."""
    from repro.analysis.threads import lint_source

    path = os.path.join(REPO, "src", "repro", "serve", "server.py")
    with open(path) as f:
        source = f.read()
    anchor = "self._closed = True"
    assert anchor in source
    bad = source.replace(
        anchor, anchor + "\n" + " " * 12 + "self._version += 1", 1)
    codes = {f.code for f in lint_source(bad, "serve/server.py")}
    assert "cross-thread-write-_version" in codes, codes


def test_thread_lint_catches_frozen_attr_write():
    """`engine` is frozen after __init__ — any later rebind, from any
    thread, is a finding."""
    from repro.analysis.threads import lint_source

    path = os.path.join(REPO, "src", "repro", "serve", "server.py")
    with open(path) as f:
        source = f.read()
    anchor = "self._closed = True"
    bad = source.replace(
        anchor, anchor + "\n" + " " * 12 + "self.engine = None", 1)
    codes = {f.code for f in lint_source(bad, "serve/server.py")}
    assert "frozen-attr-write-engine" in codes, codes


def test_thread_lint_flags_undeclared_attr():
    from repro.analysis.threads import lint_source

    src = textwrap.dedent("""\
        THREAD_METHODS = {"S.go": "client"}
        THREAD_ATTRS = {"S.x": ("client",)}

        class S:
            def __init__(self):
                self.x = 0

            def go(self):
                self.x = 1
                self.mystery = 2
        """)
    codes = {f.code for f in lint_source(src, "fixture.py")}
    assert codes == {"undeclared-attr-mystery"}, codes


# ---------------------------------------------------------------------------
# CLI — the static-audit CI contract (4 virtual devices, subprocess)
# ---------------------------------------------------------------------------

def _run_cli(*extra, timeout=600):
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *extra],
        env=ENV, cwd=REPO, capture_output=True, text=True,
        timeout=timeout)


def test_cli_exit_zero_on_committed_baseline():
    out = _run_cli("--fail-on-new")
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "0 new" in out.stdout, out.stdout[-3000:]


def test_cli_nonzero_against_empty_baseline(tmp_path):
    """The accepted R1 findings (the intentional global-bsf broadcast)
    count as NEW against an empty baseline — the gate that fails when
    anyone reintroduces the PR-5 class without a reasoned acceptance."""
    out = _run_cli("--fail-on-new",
                   "--baseline", str(tmp_path / "empty.json"))
    assert out.returncode != 0, out.stdout[-3000:]
    assert "all_gather-in-while-under-shard_map" in out.stdout


def test_cli_json_reporter():
    out = _run_cli("--rules", "T1,R6", "--json")
    assert out.returncode == 0, out.stderr[-2000:]
    doc = json.loads(out.stdout)
    assert doc["meta"]["rules"] == ["T1", "R6"]
    assert doc["new"] == [] and doc["stale"] == []
