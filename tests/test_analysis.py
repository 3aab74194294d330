"""jaxlint gates: every checker's fixture violations must be caught
(positive), their suppressed/clean twins must pass (negative), the
--ci exit-code contract must hold under violation injection, and the
committed baseline must stay in sync with the tree.

These tests never import jax-traced code — the analyzer parses source,
so each fixture is a string snippet written to a tmp tree whose layout
(``solvers/…``) marks it hot-path where a rule needs that scope.
"""

import os
import subprocess
import sys
import textwrap

from sagecal_tpu.analysis import core

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRELUDE = """\
import functools
import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, donate_argnums=(0,))
def step(x, y):
    return x + y
"""


def _lint(tmp_path, source, relpath="solvers/kernel.py"):
    path = tmp_path / relpath
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(_PRELUDE + textwrap.dedent(source))
    findings, suppressed, errors = core.run_paths(
        [str(tmp_path)], root=str(tmp_path))
    assert not errors, errors
    return findings, suppressed


def _rules(findings):
    return sorted(f.rule for f in findings)


# ---------------------------------------------------------------------------
# use-after-donate
# ---------------------------------------------------------------------------

def test_donate_read_after_call_flagged(tmp_path):
    f, _ = _lint(tmp_path, """
    def driver(y):
        x = y * 2
        out = step(x, y)
        return out + x
    """)
    assert _rules(f) == ["use-after-donate"]
    assert "read after being donated" in f[0].message


def test_donate_rebind_and_copy_twins_clean(tmp_path):
    f, _ = _lint(tmp_path, """
    def ok_rebind(y):
        x = y * 2
        x = step(x, y)
        return x

    def ok_copy(y):
        x = y * 2
        out = step(x.copy(), y)
        return out + x
    """)
    assert f == []


def test_donate_loop_without_rebind_flagged(tmp_path):
    f, _ = _lint(tmp_path, """
    def driver(y):
        x = y * 2
        out = None
        for _ in range(3):
            out = step(x, y)
        return out
    """)
    assert "use-after-donate" in _rules(f)
    assert any("inside a loop" in x.message for x in f)


def test_donate_param_and_conditional_guard_flagged(tmp_path):
    f, _ = _lint(tmp_path, """
    def bad_param(x, y):
        return step(x, y)

    def cond_guard(x, y):
        j = x.copy() if isinstance(x, jax.Array) else x
        return step(j, y)
    """)
    msgs = " | ".join(x.message for x in f)
    assert "caller-owned parameter 'x'" in msgs
    assert "may alias caller-owned x" in msgs


def test_donate_arg_tuple_escape_flagged_and_fixed_twin(tmp_path):
    f, _ = _lint(tmp_path, """
    LOG = {}

    def _call(name, jfn, *args, **kwargs):
        rec = LOG.setdefault(name, [jfn, None, 0])
        rec[1] = (args, kwargs)
        return jfn(*args, **kwargs)

    def _call_fixed(name, jfn, *args, **kwargs):
        rec = LOG.setdefault(name, [jfn, None, 0])
        rec[1] = (tuple(map(_spec, args)), kwargs)
        return jfn(*args, **kwargs)
    """)
    assert _rules(f) == ["use-after-donate"]
    assert "outliving container" in f[0].message


def test_donate_argnames_spelling_flagged(tmp_path):
    """The modern donate_argnames spelling is tracked too — resolved to
    positions through the wrapped def's signature, and matched against
    keyword call args."""
    f, _ = _lint(tmp_path, """
    def _step2(carry, y):
        return carry + y

    step2 = jax.jit(_step2, donate_argnames=("carry",))

    def driver(y):
        c = y * 2
        out = step2(c, y)
        return out + c

    def driver_kw(y):
        c = y * 3
        out = step2(y=y, carry=c)
        return out + c
    """)
    assert _rules(f) == ["use-after-donate", "use-after-donate"]


def test_hostsync_phase_context_is_not_a_gate(tmp_path):
    """`with dtrace.phase(...)` bodies execute unconditionally (null
    context when tracing is off) — a sync inside one is still a leak;
    only `if dtrace.active():` gates."""
    f, _ = _lint(tmp_path, """
    def sweep(xs, dtrace):
        tot = 0.0
        for x in xs:
            with dtrace.phase("sum"):
                tot += float(jnp.sum(x))
        return tot
    """)
    assert _rules(f) == ["host-sync"]


# ---------------------------------------------------------------------------
# retrace
# ---------------------------------------------------------------------------

def test_retrace_jit_in_loop_and_per_call_flagged(tmp_path):
    f, _ = _lint(tmp_path, """
    def run_all(xs):
        out = []
        for x in xs:
            f = jax.jit(lambda a: a + 1)
            out.append(f(x))
        return out

    def runner(x):
        f = jax.jit(lambda a: a * 2)
        return f(x)
    """)
    assert _rules(f) == ["retrace", "retrace"]
    msgs = " | ".join(x.message for x in f)
    assert "inside a loop" in msgs and "per call" in msgs


def test_retrace_factory_return_and_cache_twins_clean(tmp_path):
    f, _ = _lint(tmp_path, """
    def make_solver():
        return jax.jit(lambda a: a + 1)

    def _build_resid(fn):
        g = jax.jit(fn)
        return g

    class P:
        def __init__(self):
            self._f = jax.jit(lambda a: a)
            self._sim = None

        def run(self, x):
            if self._sim is None:
                self._sim = jax.jit(lambda a: a - 1)
            return self._sim(x)
    """)
    assert f == []


def test_retrace_nonhashable_static_flagged(tmp_path):
    f, _ = _lint(tmp_path, """
    @functools.partial(jax.jit, static_argnames=("opts",))
    def solve(x, opts):
        return x

    def use(x):
        return solve(x, opts=[1, 2])
    """)
    assert _rules(f) == ["retrace"]
    assert "static" in f[0].message


def test_retrace_tracer_control_flow_flagged(tmp_path):
    f, _ = _lint(tmp_path, """
    @jax.jit
    def body(x):
        if x > 0:
            return x
        return -x

    @jax.jit
    def body2(x):
        return float(x) + 1.0
    """)
    assert _rules(f) == ["retrace", "retrace"]


def test_retrace_static_tests_clean(tmp_path):
    f, _ = _lint(tmp_path, """
    @functools.partial(jax.jit, static_argnames=("cfg",))
    def body(x, cfg, opt=None):
        if opt is None:
            x = x + 1
        if x.shape[0] > 2:
            x = x * 2
        if cfg.flag:
            x = x - 1
        return x
    """)
    assert f == []


# ---------------------------------------------------------------------------
# host-sync (hot-path scope)
# ---------------------------------------------------------------------------

def test_hostsync_traced_and_loop_flagged(tmp_path):
    f, _ = _lint(tmp_path, """
    @jax.jit
    def kern(x):
        return np.asarray(x).sum()

    def sweep(xs):
        tot = 0.0
        for x in xs:
            tot += float(jnp.sum(x))
        return tot
    """)
    assert _rules(f) == ["host-sync", "host-sync"]


def test_hostsync_gated_and_cold_path_clean(tmp_path):
    # the dtrace.active() gate is the blessed telemetry pattern, and a
    # non-hot module (tools/) is out of scope for the host-loop rule
    f, _ = _lint(tmp_path, """
    def sweep(xs, emit):
        for x in xs:
            if dtrace.active():
                emit(float(jnp.sum(x)))
    """)
    assert f == []
    f, _ = _lint(tmp_path, """
    def sweep(xs):
        tot = 0.0
        for x in xs:
            tot += float(jnp.sum(x))
        return tot
    """, relpath="tools/offline.py")
    assert f == []


def test_hostsync_obs_gate_blessed_ungated_metric_flagged(tmp_path):
    """ISSUE 9 metrics-era twins: obs.active()-gated emission is the
    same blessed pattern as dtrace.active() (obs/metrics.py keeps the
    identical no-op-when-disabled contract), INCLUDING the combined
    ``dtrace.active() or obs.active()`` BoolOp gate — while an
    un-gated per-iteration metric read in a solver loop stays a
    finding."""
    # positive twin: un-gated float(jnp...) feeding a metric observe
    f, _ = _lint(tmp_path, """
    def sweep(xs, obs):
        for x in xs:
            obs.observe("residual", float(jnp.sum(x)))
    """)
    assert _rules(f) == ["host-sync"]
    # clean twin: the obs.active() gate
    f, _ = _lint(tmp_path, """
    def sweep(xs, obs):
        for x in xs:
            if obs.active():
                obs.observe("residual", float(jnp.sum(x)))
    """)
    assert f == []
    # clean twin: the combined gate the instrumented emit sites use
    # (solvers/sage.py, consensus/admm.py)
    f, _ = _lint(tmp_path, """
    def sweep(xs, obs, dtrace):
        for x in xs:
            if dtrace.active() or obs.active():
                v = float(jnp.sum(x))
                dtrace.emit("em_sweep", err=v)
                obs.set_gauge("err", v)
    """)
    assert f == []
    # a BoolOp mixing an active() gate with a NON-gate must not bless
    f, _ = _lint(tmp_path, """
    def sweep(xs, obs, verbose):
        for x in xs:
            if obs.active() or verbose:
                obs.observe("residual", float(jnp.sum(x)))
    """)
    assert _rules(f) == ["host-sync"]


def test_obs_package_is_hot_path_scope():
    """ISSUE 9: obs/ joined the hot-path scope — the metrics layer
    runs inside every loop it instruments, so an un-gated device read
    there is exactly as costly as one in the loop itself."""
    assert core.is_hot_path("sagecal_tpu/obs/metrics.py")
    assert core.is_hot_path("sagecal_tpu/obs/health.py")
    assert not core.is_hot_path("sagecal_tpu/tools/fits.py")


def test_hostsync_faults_gate_blessed_and_faults_hot_scope(tmp_path):
    """ISSUE 10: the fault-injection harness keeps the
    no-op-when-disabled contract, so ``faults.active()`` blesses a
    gated block exactly like ``dtrace.active()``/``obs.active()`` —
    and faults.py itself sits in the hot-path scope (the retry layer
    wraps every I/O seam's hot loop)."""
    assert core.is_hot_path("sagecal_tpu/faults.py")
    # clean twin: a faults.active()-gated sync in a hot loop
    f, _ = _lint(tmp_path, """
    def sweep(xs, faults, poison):
        for x in xs:
            if faults.active():
                poison(float(jnp.sum(x)))
    """)
    assert f == []
    # positive twin: the same sync un-gated stays a finding
    f, _ = _lint(tmp_path, """
    def sweep(xs, poison):
        for x in xs:
            poison(float(jnp.sum(x)))
    """)
    assert _rules(f) == ["host-sync"]


def test_hostsync_block_in_loop_flagged_async_readback_blessed(tmp_path):
    """ISSUE 5 overlap contract: a per-iteration block_until_ready in
    a hot host loop is a finding, while the BLESSED async-readback API
    (.copy_to_host_async, started before handing the fetch to the
    sched writer thread) must never be — not now, not via a future
    broadening of the attribute-pattern rules."""
    f, _ = _lint(tmp_path, """
    def drain(xs):
        outs = []
        for x in xs:
            r = step(x, x)
            jax.block_until_ready(r)
            outs.append(r)
        return outs
    """)
    assert _rules(f) == ["host-sync"]
    assert "block_until_ready" in f[0].message

    f, _ = _lint(tmp_path, """
    def overlapped(xs, submit):
        for x in xs:
            r = step(x, x)
            r.copy_to_host_async()
            submit(r)
    """)
    assert f == []
    # sched.py itself is hot-path scope now (core._HOT_BASENAMES): the
    # writer/prefetch thread loops must never grow a per-iteration sync
    f, _ = _lint(tmp_path, """
    def worker(q):
        while True:
            r = q.get()
            r.item()
    """, relpath="sched.py")
    assert _rules(f) == ["host-sync"]


def test_hostsync_block_in_loop_suppressed_with_reason_ok(tmp_path):
    """The deliberate per-sweep timing barrier (sage.py's fuse=auto
    plan learning) stays expressible: an inline suppression WITH a
    reason silences the block_until_ready finding."""
    f, s = _lint(tmp_path, """
    def sweeps(xs):
        for x in xs:
            r = step(x, x)
            # jaxlint: disable=host-sync -- per-sweep timing barrier
            jax.block_until_ready(r)
    """)
    assert f == []
    assert len(s) == 1 and "timing barrier" in s[0][1]


# ---------------------------------------------------------------------------
# dtype-promotion (traced bodies in hot modules)
# ---------------------------------------------------------------------------

def test_dtype_promotion_flagged(tmp_path):
    f, _ = _lint(tmp_path, """
    @jax.jit
    def kern(x):
        scale = jnp.zeros((4,))
        return x * scale

    @jax.jit
    def widen(x):
        return x.astype(jnp.complex128)
    """)
    assert _rules(f) == ["dtype-promotion", "dtype-promotion"]


def test_dtype_derivation_and_explicit_clean(tmp_path):
    f, _ = _lint(tmp_path, """
    @jax.jit
    def kern(x):
        scale = jnp.zeros((4,), x.dtype)
        cdt = jnp.complex64 if x.dtype == jnp.float32 else jnp.complex128
        return (x * scale).astype(cdt)

    def host_staging(xs):
        return jnp.zeros((4,))
    """)
    assert f == []


# ---------------------------------------------------------------------------
# storage-accum (the dtype-policy storage/accumulate boundary, ISSUE 6)
# ---------------------------------------------------------------------------

def test_storage_accum_silent_reduction_flagged(tmp_path):
    f, _ = _lint(tmp_path, """
    from sagecal_tpu import dtypes as dtp

    @jax.jit
    def kern(x8, wt, st):
        xs = dtp.to_storage(x8, st)
        rw = xs * wt
        total = jnp.sum(rw * rw)
        gram = jnp.einsum("bi,bj->ij", rw, rw)
        return total, gram
    """)
    assert _rules(f) == ["storage-accum", "storage-accum"]
    assert "f32 accumulator" in f[0].message


def test_storage_accum_scatter_flagged(tmp_path):
    f, _ = _lint(tmp_path, """
    from sagecal_tpu import dtypes as dtp

    @jax.jit
    def kern(x8, idx):
        st = x8.dtype
        r = x8.astype(st) * 2.0
        acc0 = jnp.zeros((4,), st)
        return acc0.at[idx].add(r)
    """)
    assert _rules(f) == ["storage-accum"]
    assert "scatter-accumulation" in f[0].message


def test_storage_accum_suppressed_twin(tmp_path):
    f, s = _lint(tmp_path, """
    from sagecal_tpu import dtypes as dtp

    @jax.jit
    def kern(x8, st):
        xs = dtp.to_storage(x8, st)
        # jaxlint: disable=storage-accum -- 8-element row reduce, exact in bf16
        return jnp.sum(xs * xs)
    """)
    assert f == []
    assert len(s) == 1 and s[0][0].rule == "storage-accum"


def test_storage_accum_clean_twins(tmp_path):
    f, _ = _lint(tmp_path, """
    from sagecal_tpu import dtypes as dtp

    @jax.jit
    def kern(x8, wt, st):
        pet = dtp.pet(st)
        xs = dtp.to_storage(x8, st)
        rw = xs * wt
        gram = jnp.einsum("bi,bj->ij", rw, rw, **pet)          # ** splat
        named = jnp.einsum("bi,bj->ij", rw, rw,
                           preferred_element_type=jnp.float32)  # explicit
        rca = dtp.acc(rw)
        total = jnp.sum(rca * rca)                              # upcast
        upc = jnp.sum(rw.astype(jnp.float32) ** 2)              # astype acc
        return gram, named, total, upc

    @jax.jit
    def untouched(x8):
        # no storage casts in scope: the rule never seeds from params
        return jnp.sum(x8 * x8)
    """)
    assert f == []


def test_storage_accum_pallas_kernel_flagged(tmp_path):
    """A Pallas kernel body is traced code (pl.pallas_call joined
    _TRACE_WRAPPERS with the ISSUE 11 ops/ scope): a reduced-dtype
    kernel accumulator — summing planes still in the storage dtype —
    is exactly the bug class the rule exists for."""
    f, _ = _lint(tmp_path, """
    from jax.experimental import pallas as pl
    from sagecal_tpu import dtypes as dtp

    def _kern(x_ref, o_ref, st):
        xs = dtp.to_storage(x_ref[...], st)
        o_ref[...] += jnp.sum(xs * xs, axis=0)

    def sweep(x, st):
        def kernel(x_ref, o_ref):
            _kern(x_ref, o_ref, st)
        return pl.pallas_call(
            kernel, grid=(4,),
            out_shape=jax.ShapeDtypeStruct((8,), jnp.float32))(x)
    """, relpath="ops/kern_pallas.py")
    assert _rules(f) == ["storage-accum"]


def test_storage_accum_pallas_kernel_clean_twin(tmp_path):
    """The blessed kernel shape: quantize-at-load then upcast — the
    block read rounds to storage and IMMEDIATELY casts to the acc
    dtype, so every accumulation below is f32."""
    f, _ = _lint(tmp_path, """
    from jax.experimental import pallas as pl
    from sagecal_tpu import dtypes as dtp

    def _kern(x_ref, o_ref, st, acc):
        xs = dtp.to_storage(x_ref[...], st).astype(acc)
        o_ref[...] += jnp.sum(xs * xs, axis=0)

    def sweep(x, st, acc):
        def kernel(x_ref, o_ref):
            _kern(x_ref, o_ref, st, acc)
        return pl.pallas_call(
            kernel, grid=(4,),
            out_shape=jax.ShapeDtypeStruct((8,), jnp.float32))(x)
    """, relpath="ops/kern_pallas.py")
    assert f == []


def test_ops_scope_is_hot():
    """ISSUE 11 scope widening: ops/ (the Pallas kernels) is hot-path
    territory for the dtype/storage rules."""
    assert core.is_hot_path("sagecal_tpu/ops/coh_pallas.py")


# ---------------------------------------------------------------------------
# cond-cost
# ---------------------------------------------------------------------------

def test_condcost_inlined_heavy_branch_flagged(tmp_path):
    f, _ = _lint(tmp_path, """
    def outer(x, w):
        def heavy():
            return jnp.einsum("ij,jk->ik", x, w)
        return jax.lax.cond(x.ndim > 1, lambda: x, heavy)
    """)
    assert _rules(f) == ["cond-cost"]
    assert "einsum" in f[0].message


def test_condcost_module_level_branch_clean(tmp_path):
    f, _ = _lint(tmp_path, """
    def _mm(x, w):
        return jnp.einsum("ij,jk->ik", x, w)

    def outer(x, w):
        def fwd():
            # forwarding through a module-level priceable boundary
            return _mm(x, w)
        return jax.lax.cond(x.ndim > 1, lambda: x, fwd)

    def cheap(x):
        return jax.lax.cond(x.ndim > 1, lambda: jnp.where(x > 0, x, 0.0),
                            lambda: x)
    """)
    assert f == []


# ---------------------------------------------------------------------------
# suppression syntax
# ---------------------------------------------------------------------------

def test_suppression_with_reason_silences(tmp_path):
    f, supp = _lint(tmp_path, """
    def sweep(xs):
        tot = 0.0
        for x in xs:
            # jaxlint: disable=host-sync -- convergence check needs it
            tot += float(jnp.sum(x))
        return tot
    """)
    assert f == []
    assert len(supp) == 1
    assert supp[0][1] == "convergence check needs it"


def test_suppression_without_reason_is_a_finding(tmp_path):
    f, supp = _lint(tmp_path, """
    def sweep(xs):
        tot = 0.0
        for x in xs:
            # jaxlint: disable=host-sync
            tot += float(jnp.sum(x))
        return tot
    """)
    assert "suppression" in _rules(f)
    # and the reasonless directive does NOT silence the finding
    assert "host-sync" in _rules(f)


def test_suppression_unknown_rule_is_a_finding(tmp_path):
    f, _ = _lint(tmp_path, """
    X = 1  # jaxlint: disable=not-a-rule -- whatever
    """)
    assert "suppression" in _rules(f)


# ---------------------------------------------------------------------------
# baseline + the --ci gate
# ---------------------------------------------------------------------------

def test_baseline_in_sync_with_tree():
    """The committed baseline pins exactly the tree's accepted
    findings: no NEW finding (the gate must be green at HEAD) and no
    STALE entry (fixed violations leave the baseline), and every entry
    carries a written reason."""
    findings, _, errors = core.run_paths(
        [os.path.join(REPO, "sagecal_tpu")], root=REPO)
    assert not errors, errors
    baseline = core.load_baseline(os.path.join(REPO, core.BASELINE_NAME))
    new, stale = core.diff_baseline(findings, baseline)
    assert not new, "unbaselined finding(s):\n" + "\n".join(
        f.render() for f in new)
    assert not stale, f"stale baseline entr(ies): {stale}"
    missing = [e for e in baseline.values() if not e.get("reason")]
    assert not missing, f"baseline entries without a reason: {missing}"


_VIOLATIONS = {
    "use-after-donate": """
    def driver(y):
        x = y * 2
        out = step(x, y)
        return out + x
    """,
    "retrace": """
    def runner(x):
        f = jax.jit(lambda a: a * 2)
        return f(x)
    """,
    "host-sync": """
    def sweep(xs):
        tot = 0.0
        for x in xs:
            tot += float(jnp.sum(x))
        return tot
    """,
    "dtype-promotion": """
    @jax.jit
    def kern(x):
        return x * jnp.zeros((4,))
    """,
    "cond-cost": """
    def outer(x, w):
        def heavy():
            return jnp.einsum("ij,jk->ik", x, w)
        return jax.lax.cond(x.ndim > 1, lambda: x, heavy)
    """,
    "shared-state": """
    import threading

    class Pump:
        def __init__(self):
            self.items = []
            self._thread = threading.Thread(target=self._run,
                                            name="pump-loop")

        def _run(self):
            self.items.append(1)

        def push(self, x):
            self.items.append(x)
    """,
    "lock-order": """
    import threading

    class Banks:
        def __init__(self):
            self.a_lock = threading.Lock()
            self.b_lock = threading.Lock()

        def first(self):
            with self.a_lock:
                with self.b_lock:
                    pass

        def second(self):
            with self.b_lock:
                with self.a_lock:
                    pass
    """,
    "handoff-ownership": """
    def produce(q, n):
        batch = [n]
        q.put(batch)
        batch.append(n + 1)
    """,
    "scope-discipline": """
    def bad(dtrace, tracer):
        s = dtrace.scope(tracer)
        return s
    """,
}


def test_ci_gate_green_on_tree():
    r = subprocess.run(
        [sys.executable, "-m", "sagecal_tpu.analysis", "--ci"],
        cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr


def test_ci_gate_fails_on_injected_violations(tmp_path):
    """Acceptance: --ci exits non-zero when any checker's fixture
    violation is injected into the scanned set."""
    for rule, src in _VIOLATIONS.items():
        d = tmp_path / rule.replace("-", "_") / "solvers"
        d.mkdir(parents=True)
        (d / "bad.py").write_text(_PRELUDE + textwrap.dedent(src))
        r = subprocess.run(
            [sys.executable, "-m", "sagecal_tpu.analysis", "--ci",
             str(d.parent)],
            cwd=REPO, capture_output=True, text=True)
        assert r.returncode != 0, (rule, r.stdout, r.stderr)
        assert rule in r.stdout, (rule, r.stdout)


# ---------------------------------------------------------------------------
# threadlint: shared-state (ISSUE 19)
# ---------------------------------------------------------------------------

def test_shared_state_two_roles_unguarded_flagged(tmp_path):
    f, _ = _lint(tmp_path, """
    import threading

    class Pump:
        def __init__(self):
            self.items = []
            self._thread = threading.Thread(target=self._run,
                                            name="pump-loop")

        def _run(self):
            self.items.append(1)

        def push(self, x):
            self.items.append(x)
    """)
    assert _rules(f) == ["shared-state"]
    assert "pump-loop" in f[0].message and "caller" in f[0].message


def test_shared_state_lock_guarded_twin_clean(tmp_path):
    f, _ = _lint(tmp_path, """
    import threading

    class Pump:
        def __init__(self):
            self.items = []
            self._lock = threading.Lock()
            self._thread = threading.Thread(target=self._run,
                                            name="pump-loop")

        def _run(self):
            with self._lock:
                self.items.append(1)

        def push(self, x):
            with self._lock:
                self.items.append(x)
    """)
    assert f == []


def test_shared_state_role_annotation_unifies(tmp_path):
    """A '# thread-role:' annotation declaring the true role silences
    the finding: both writers are the SAME thread."""
    f, _ = _lint(tmp_path, """
    import threading

    class Pump:
        def __init__(self):
            self.items = []
            self._thread = threading.Thread(target=self._run,
                                            name="pump-loop")

        def _run(self):
            self.items.append(1)

        # thread-role: pump-loop
        def flush(self):
            self.items.clear()
    """)
    assert f == []


def test_shared_state_suppressed_twin(tmp_path):
    f, supp = _lint(tmp_path, """
    import threading

    class Pump:
        def __init__(self):
            self.items = []
            self._thread = threading.Thread(target=self._run,
                                            name="pump-loop")

        def _run(self):
            # jaxlint: disable=shared-state -- append is atomic here
            self.items.append(1)

        def push(self, x):
            self.items.append(x)
    """)
    assert f == []
    assert len(supp) == 1


def test_parse_thread_roles_grammar():
    lines = [
        "# thread-role: writer",
        "def close(self):",
        "    pass",
        "def other(self):  # thread-role: a, b",
        "    pass",
    ]
    roles = core.parse_thread_roles(lines)
    assert roles[2] == ("writer",)     # standalone: next code line
    assert roles[4] == ("a", "b")      # trailing: its own line


# ---------------------------------------------------------------------------
# threadlint: lock-order
# ---------------------------------------------------------------------------

def test_lock_order_cycle_flagged(tmp_path):
    f, _ = _lint(tmp_path, """
    import threading

    class Banks:
        def __init__(self):
            self.a_lock = threading.Lock()
            self.b_lock = threading.Lock()

        def first(self):
            with self.a_lock:
                with self.b_lock:
                    pass

        def second(self):
            with self.b_lock:
                with self.a_lock:
                    pass
    """)
    assert _rules(f) == ["lock-order"]
    assert "cycle" in f[0].message


def test_lock_order_consistent_twin_clean(tmp_path):
    f, _ = _lint(tmp_path, """
    import threading

    class Banks:
        def __init__(self):
            self.a_lock = threading.Lock()
            self.b_lock = threading.Lock()

        def first(self):
            with self.a_lock:
                with self.b_lock:
                    pass

        def second(self):
            with self.a_lock:
                with self.b_lock:
                    pass
    """)
    assert f == []


def test_lock_order_call_through_cycle_flagged(tmp_path):
    """The edge walks through a same-class call: holding A while
    calling a method that takes B, against a direct B->A nest."""
    f, _ = _lint(tmp_path, """
    import threading

    class Banks:
        def __init__(self):
            self.a_lock = threading.Lock()
            self.b_lock = threading.Lock()

        def deposit(self):
            with self.a_lock:
                self._audit()

        def _audit(self):
            with self.b_lock:
                pass

        def sweep(self):
            with self.b_lock:
                with self.a_lock:
                    pass
    """)
    assert "lock-order" in _rules(f)


def test_lock_order_nonreentrant_self_nest_flagged(tmp_path):
    f, _ = _lint(tmp_path, """
    import threading

    class Reent:
        def __init__(self):
            self._lock = threading.Lock()

        def outer(self):
            with self._lock:
                self.inner()

        def inner(self):
            with self._lock:
                pass
    """)
    assert _rules(f) == ["lock-order"]
    assert "reacquisition" in f[0].message


def test_lock_order_rlock_self_nest_clean(tmp_path):
    f, _ = _lint(tmp_path, """
    import threading

    class Reent:
        def __init__(self):
            self._rl = threading.RLock()

        def outer(self):
            with self._rl:
                self.inner()

        def inner(self):
            with self._rl:
                pass
    """)
    assert f == []


# ---------------------------------------------------------------------------
# threadlint: handoff-ownership
# ---------------------------------------------------------------------------

def test_handoff_mutate_after_put_flagged(tmp_path):
    f, _ = _lint(tmp_path, """
    def produce(q, n):
        batch = [n]
        q.put(batch)
        batch.append(n + 1)
    """)
    assert _rules(f) == ["handoff-ownership"]
    assert "consumer owns it" in f[0].message


def test_handoff_read_after_ring_stage_flagged(tmp_path):
    """Ring slots are DONATED by the consumer: even a read after
    stage() is use-after-donate on a host handle."""
    f, _ = _lint(tmp_path, """
    def stage_it(ring, tag, buf):
        ring.stage(tag, buf)
        return buf.shape
    """)
    assert _rules(f) == ["handoff-ownership"]


def test_handoff_rebind_and_fresh_twins_clean(tmp_path):
    f, _ = _lint(tmp_path, """
    def produce_rebind(q, n):
        batch = [n]
        q.put(batch)
        batch = [n + 1]
        batch.append(n + 2)

    def produce_fresh(q, n):
        q.put(list(range(n)))

    def read_after_put_ok(q, n):
        batch = [n]
        q.put(batch)
        return len(batch)
    """)
    assert f == []


def test_handoff_loop_carried_mutation_flagged(tmp_path):
    """A mutation BEFORE the put inside a loop is after it on the next
    iteration — the carried handle is still the consumer's."""
    f, _ = _lint(tmp_path, """
    def pump(q, xs):
        batch = []
        for x in xs:
            batch.append(x)
            q.put(batch)
    """)
    assert _rules(f) == ["handoff-ownership"]


def test_handoff_suppressed_twin(tmp_path):
    f, supp = _lint(tmp_path, """
    def produce(q, n):
        batch = [n]
        q.put(batch)
        # jaxlint: disable=handoff-ownership -- consumer copies on get
        batch.append(n + 1)
    """)
    assert f == []
    assert len(supp) == 1


# ---------------------------------------------------------------------------
# threadlint: scope-discipline
# ---------------------------------------------------------------------------

def test_scope_outside_with_flagged(tmp_path):
    f, _ = _lint(tmp_path, """
    def bad(dtrace, tracer):
        s = dtrace.scope(tracer)
        return s
    """)
    assert _rules(f) == ["scope-discipline"]


def test_scope_spawn_inside_scope_flagged(tmp_path):
    f, _ = _lint(tmp_path, """
    import threading

    def bad(dtrace, tracer, fn):
        with dtrace.scope(tracer):
            t = threading.Thread(target=fn)
            t.start()
    """)
    assert _rules(f) == ["scope-discipline"]
    assert "does NOT extend" in f[0].message


def test_scope_clean_twins(tmp_path):
    """with-entry, factory return, and context= spawn factories are
    the three blessed forms."""
    f, _ = _lint(tmp_path, """
    def ok_with(dtrace, tracer):
        with dtrace.scope(tracer):
            pass

    def ok_factory(dtrace, tracer):
        return dtrace.scope(tracer)

    def ok_prefetch(Prefetcher, dtrace, produce, tracer):
        with dtrace.scope(tracer):
            return Prefetcher(produce,
                              context=lambda: dtrace.scope(tracer))
    """)
    assert f == []


def test_scope_prefetcher_without_context_flagged(tmp_path):
    f, _ = _lint(tmp_path, """
    def bad(Prefetcher, dtrace, produce, tracer):
        with dtrace.scope(tracer):
            return Prefetcher(produce)
    """)
    assert _rules(f) == ["scope-discipline"]
    assert "context=" in f[0].message


# ---------------------------------------------------------------------------
# stale-suppression audit (ISSUE 19 satellite)
# ---------------------------------------------------------------------------

def test_stale_suppression_is_a_finding(tmp_path):
    """A disable whose rule no longer fires on its target line is dead
    armor: it would silently swallow a FUTURE real finding there."""
    f, _ = _lint(tmp_path, """
    def fine(x):
        return x + 1  # jaxlint: disable=host-sync -- was needed pre-refactor
    """)
    assert "suppression" in _rules(f)
    assert "stale" in f[0].message


def test_live_suppression_not_stale(tmp_path):
    # the matched case is test_suppression_with_reason_silences: a
    # directive whose rule DOES fire produces neither finding
    f, supp = _lint(tmp_path, """
    def sweep(xs):
        tot = 0.0
        for x in xs:
            # jaxlint: disable=host-sync -- convergence check needs it
            tot += float(jnp.sum(x))
        return tot
    """)
    assert f == []
    assert len(supp) == 1
