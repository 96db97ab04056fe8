"""graftlint (ISSUE 10): golden bad-code fixtures per rule, the clean-tree
tier-1 gate, and the J2 census cross-check against the (2,2)-mesh dryrun
programs.

Three layers of pins:

1. **Golden fixtures** — for every rule (R1-R4, J1-J2) a minimal bad
   module/program makes the rule fire with the right rule id and
   ``path:line``, and a minimally-corrected twin stays clean — the rules
   detect the defect CLASS, not an incidental pattern of today's tree.
2. **Clean tree** — the AST layer over the shipped package and the jaxpr
   layer over the canonical small-schema programs produce ZERO findings
   against the committed (empty) GRAFTLINT_BASELINE.json.  This is the
   tier-1 integration the pre-merge ``scripts/graftlint.py --check``
   mirrors; jaxpr traces are cached per session (driver lru_cache), so
   the layer prices one trace pass per pytest run.
3. **Census cross-check** (ISSUE 10 acceptance) — the jaxpr collective
   census of the (2,2)-mesh data/hybrid/voting grow programs agrees with
   the telemetry wire-site inventory recorded while tracing them (the
   same inventory ``__graft_entry__.measure_wire_bytes`` prices and
   perf_gate gates), and with any recorded MULTICHIP_WIRE site inventory
   found in MULTICHIP_r*.json.
"""
import glob
import json
import os
import subprocess
import sys
import textwrap

import pytest

from lightgbm_tpu.analysis import (Baseline, GraftlintError, LintConfig,
                                   RULES, default_baseline_path,
                                   run_ast_rules)
from lightgbm_tpu.analysis import driver as gl_driver
from lightgbm_tpu.analysis.findings import Finding, split_baseline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint(src, path="fixture.py", **cfg):
    return run_ast_rules({path: textwrap.dedent(src)},
                         LintConfig(**cfg) if cfg else None)


# ===================================================== R1: seam coverage

R1_BAD = """
import jax

def leaf_sum(x, axis):
    return jax.lax.psum(x, axis)
"""

R1_OK = """
import functools
import jax
from lightgbm_tpu import telemetry

_c = functools.partial(telemetry.collective_span, axis="data")

def build(site):
    def seam(h):
        return jax.lax.psum(h, "data")
    wrapped = _c(site, seam, kind="psum")
    other = telemetry.collective_span(
        "s2", lambda h: jax.lax.psum_scatter(h, "data"), kind="psum_scatter")
    return wrapped, other

def recorded(x):
    telemetry.record_collective("site", "pmax", "data", 4)
    return jax.lax.pmax(x, "data")
"""


def test_r1_fires_on_raw_collective():
    (f,) = _lint(R1_BAD)
    assert f.rule == "R1" and f.path == "fixture.py" and f.line == 5
    assert f.site == "lax.psum" and f.symbol == "leaf_sum"


def test_r1_clean_on_all_three_coverage_forms():
    # partial-alias wrap, direct collective_span lambda, record_collective
    assert _lint(R1_OK) == []


R1_NAME_COLLISION = """
import jax
from lightgbm_tpu import telemetry

def wrapped_home():
    def _reduce(h):
        return jax.lax.psum(h, "data")
    return telemetry.collective_span("site", _reduce, kind="psum")

def unwrapped_home():
    def _reduce(h):
        return jax.lax.psum(h, "data")
    return _reduce
"""


def test_r1_wrap_coverage_is_scope_local_not_name_global():
    # a wrapped function name in one scope must not cover a same-named
    # unwrapped function elsewhere in the module
    (f,) = _lint(R1_NAME_COLLISION)
    assert f.rule == "R1" and f.symbol == "unwrapped_home._reduce"
    assert f.line == 12


# ================================================ R2: cache-key complete

R2_BAD = """
from lightgbm_tpu.ops.compact import partition_overlap_on
_MY_PROGRAMS = {}

def get_program(n):
    overlap = partition_overlap_on()
    key = (n,)
    prog = _MY_PROGRAMS.get(key)
    if prog is None:
        prog = make(n, overlap)
        _MY_PROGRAMS[key] = prog
    return prog
"""

R2_OK = """
from lightgbm_tpu.ops.compact import partition_overlap_on
_MY_PROGRAMS = {}

def get_program(n):
    use_pp = n > 2 and partition_overlap_on()
    key = (n, use_pp)
    prog = _MY_PROGRAMS.get(key)
    if prog is None:
        prog = make(n, use_pp)
        _MY_PROGRAMS[key] = prog
    return prog
"""

R2_READ_BAD = """
_MY_PROGRAMS = {}

def get_program(self, n):
    mesh = make_mesh(getattr(self.config, "device_type", ""))
    key = (n, mesh.size)
    _MY_PROGRAMS[key] = build(mesh)
    return _MY_PROGRAMS[key]
"""


def test_r2_fires_on_key_missing_resolved_call():
    (f,) = _lint(R2_BAD)
    assert f.rule == "R2" and f.site == "partition_overlap_on()"
    assert f.symbol == "get_program" and f.line == 6


def test_r2_clean_when_key_carries_the_bit_through_a_local():
    assert _lint(R2_OK) == []


def test_r2_fires_on_laundered_device_type_read():
    # mesh.size DERIVES from device_type but loses its identity — two
    # backends with equal device counts would collide on the key (the
    # exact FP chunk-program gap this PR fixed in parallel/learners.py)
    (f,) = _lint(R2_READ_BAD)
    assert f.rule == "R2" and f.site == "device_type"


# the booster's resolved mixed-bin layout spec is a cache-key bit like
# the kernel-routing flags (ISSUE 12): the traced program bakes the
# per-class histogram pass structure (and, block-locally, the canonical
# reorder gathers) in, so a cached program built while reading
# ``_pack_spec`` must thread the spec (or a digest) into its key
R2_PACK_BAD = """
_MY_PROGRAMS = {}

def get_program(self, gbdt, n):
    packing = getattr(gbdt, "_pack_spec", None)
    key = (n,)
    _MY_PROGRAMS[key] = build(n, packing)
    return _MY_PROGRAMS[key]
"""

R2_PACK_OK = """
_MY_PROGRAMS = {}

def get_program(self, gbdt, n):
    packing = getattr(gbdt, "_pack_spec", None)
    key = (n, packing)
    _MY_PROGRAMS[key] = build(n, packing)
    return _MY_PROGRAMS[key]
"""


def test_r2_fires_on_unkeyed_pack_spec_read():
    (f,) = _lint(R2_PACK_BAD)
    assert f.rule == "R2" and f.site == "_pack_spec"
    assert f.symbol == "get_program"


def test_r2_clean_when_pack_spec_rides_the_key():
    assert _lint(R2_PACK_OK) == []


# ======================================================= R3: span fences

R3_BAD = """
from lightgbm_tpu import telemetry

def predict(prog, x):
    with telemetry.span("predict"):
        return prog(x)
"""

R3_OK = """
from lightgbm_tpu import telemetry

def predict(prog, x):
    with telemetry.span("predict") as sp:
        return sp.fence(prog(x))

def readback(dev):
    with telemetry.span("model_readback"):
        return fetch(dev)
"""


def test_r3_fires_on_unfenced_device_span():
    (f,) = _lint(R3_BAD)
    assert f.rule == "R3" and f.line == 5 and f.site == "span('predict')"


def test_r3_clean_when_fenced_and_for_host_spans():
    assert _lint(R3_OK) == []


# ============================================ R4: banned in traced code

R4_BAD = """
import numpy as np
import time
import jax.numpy as jnp

def traced(x):
    t = time.time()
    r = np.random.rand(4)
    y = x.astype(jnp.float64)
    return t, r, y

def sized(n):
    return jnp.zeros((n,), dtype="float64")
"""


def test_r4_fires_on_each_banned_pattern():
    found = _lint(R4_BAD, path="fix_r4.py",
                  traced_suffixes=("fix_r4.py",))
    sites = {f.site for f in found}
    assert all(f.rule == "R4" for f in found)
    assert "time.time" in sites
    assert "np.random.rand" in sites
    assert "jnp.float64" in sites
    assert 'dtype="float64"' in sites


def test_r4_scoped_to_traced_modules_only():
    # same source outside the traced-module set is host-side code
    assert _lint(R4_BAD, path="host_helper.py",
                 traced_suffixes=("fix_r4.py",)) == []


R4_NESTED = """
import numpy as np

def outer(x):
    def inner(y):
        return np.sum(y)
    return inner(x)
"""


def test_r4_reports_nested_closure_violations_exactly_once():
    # one violation inside a nested closure must yield ONE finding,
    # attributed to the innermost function — not once per enclosing level
    found = _lint(R4_NESTED, path="fix_r4.py",
                  traced_suffixes=("fix_r4.py",))
    assert len(found) == 1
    assert found[0].symbol == "outer.inner" and found[0].site == "np.sum"


# ============================================ J1: jaxpr dtype discipline

@pytest.fixture(scope="module")
def jax_mod():
    import jax
    import jax.numpy as jnp
    return jax, jnp


def test_j1_fires_on_float_contamination_of_int_chain(jax_mod):
    jax, jnp = jax_mod
    from lightgbm_tpu.analysis.jaxpr_rules import check_dtype_discipline

    def bad(v):
        f = v.astype(jnp.float32)       # int8 -> f32: contamination
        return jax.lax.psum(f.astype(jnp.int32), "data")

    jaxpr = jax.make_jaxpr(bad, axis_env=[("data", 2)])(
        jnp.zeros((4,), jnp.int8))
    found = check_dtype_discipline(jaxpr, program="fix/int_chain",
                                   feature_width=12, bin_width=16)
    assert any(f.rule == "J1" and "float conversion" in f.message
               for f in found)


def test_j1_follows_contamination_across_a_loop_carry(jax_mod):
    # the int8 accumulator psum lives inside scan/fori bodies in the real
    # programs — contamination introduced OUTSIDE and carried in must
    # still be caught (backward slice follows sub-jaxpr invar bindings
    # out to the enclosing eqn's operands)
    jax, jnp = jax_mod
    from lightgbm_tpu.analysis.jaxpr_rules import check_dtype_discipline

    def bad(v):
        poisoned = v.astype(jnp.float32).astype(jnp.int32)

        def body(carry, _):
            return jax.lax.psum(carry, "data"), None

        out, _ = jax.lax.scan(body, poisoned, None, length=2)
        return out

    jaxpr = jax.make_jaxpr(bad, axis_env=[("data", 2)])(
        jnp.zeros((4,), jnp.int8))
    found = check_dtype_discipline(jaxpr, program="fix/carry",
                                   feature_width=12, bin_width=16)
    assert any(f.rule == "J1" and "float conversion" in f.message
               for f in found)


def test_j1_clean_on_pure_int_chain_with_quantize_boundary(jax_mod):
    jax, jnp = jax_mod
    from lightgbm_tpu.analysis.jaxpr_rules import check_dtype_discipline

    def good(g):
        q = jnp.clip(jnp.round(g * 4.0), -127, 127).astype(jnp.int8)
        return jax.lax.psum(q.astype(jnp.int32), "data")

    jaxpr = jax.make_jaxpr(good, axis_env=[("data", 2)])(
        jnp.zeros((4,), jnp.float32))
    assert check_dtype_discipline(jaxpr, program="fix/quantized",
                                  feature_width=12, bin_width=16) == []


def test_j1_fires_on_id_narrowing_below_global_width(jax_mod):
    jax, jnp = jax_mod
    from lightgbm_tpu.analysis.jaxpr_rules import check_dtype_discipline

    def bad(ids):
        return ids.astype(jnp.bfloat16)   # 256-exact < F_global=300

    jaxpr = jax.make_jaxpr(bad)(jnp.zeros((4,), jnp.int32))
    found = check_dtype_discipline(jaxpr, program="fix/narrow",
                                   feature_width=300, bin_width=16)
    assert any(f.rule == "J1" and "narrowing" in f.message for f in found)
    # the same convert is SAFE when the global width fits bf16 exactly
    assert check_dtype_discipline(jaxpr, program="fix/narrow_ok",
                                  feature_width=28, bin_width=255) == []


# =========================================== J2: jaxpr collective census

def test_j2_fires_on_unwrapped_collective(jax_mod):
    jax, jnp = jax_mod
    from lightgbm_tpu.analysis.jaxpr_rules import (check_collective_census,
                                                   trace_census)

    def raw(x):
        return jax.lax.psum(x, "data")

    with trace_census() as holder:
        jaxpr = jax.make_jaxpr(raw, axis_env=[("data", 2)])(jnp.zeros((4,)))
    found = check_collective_census("fix/raw", jaxpr, holder.sites)
    assert any(f.rule == "J2" and f.site == "psum"
               and "ZERO declared" in f.message for f in found)


def test_j2_generic_reduce_covers_only_reduction_kinds(jax_mod):
    # wrap_schedule's fallback kind="reduce" may stand in for psum/pmax —
    # never for an all_gather, and a generic record with NO reduction
    # eqns at all is itself stale
    jax, jnp = jax_mod
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.analysis.jaxpr_rules import (check_collective_census,
                                                   trace_census)

    def gathered(x):
        telemetry.record_collective("seam", "reduce", "data", 4)
        return jax.lax.all_gather(x, "data")

    with trace_census() as holder:
        jaxpr = jax.make_jaxpr(gathered, axis_env=[("data", 2)])(
            jnp.zeros((4,)))
    found = check_collective_census("fix/generic", jaxpr, holder.sites)
    assert any(f.rule == "J2" and f.site == "all_gather" for f in found)

    def no_collectives(x):
        telemetry.record_collective("seam", "reduce", "data", 4)
        return x + 1.0

    with trace_census() as holder:
        jaxpr = jax.make_jaxpr(no_collectives)(jnp.zeros((4,)))
    found = check_collective_census("fix/generic_stale", jaxpr,
                                    holder.sites)
    assert any(f.rule == "J2" and f.site == "reduce" for f in found)

    def reduced(x):
        telemetry.record_collective("seam", "reduce", "data", 4)
        return jax.lax.psum(x, "data")

    with trace_census() as holder:
        jaxpr = jax.make_jaxpr(reduced, axis_env=[("data", 2)])(
            jnp.zeros((4,)))
    assert check_collective_census("fix/generic_ok", jaxpr,
                                   holder.sites) == []


def test_j2_fires_on_stale_declared_site(jax_mod):
    jax, jnp = jax_mod
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.analysis.jaxpr_rules import (check_collective_census,
                                                   trace_census)

    def stale(x):
        telemetry.record_collective("ghost", "all_gather", "data", 4)
        return x + 1.0

    with trace_census() as holder:
        jaxpr = jax.make_jaxpr(stale)(jnp.zeros((4,)))
    found = check_collective_census("fix/stale", jaxpr, holder.sites)
    assert any(f.rule == "J2" and f.site == "all_gather"
               and "contains none" in f.message for f in found)


def test_trace_census_restores_telemetry_state(jax_mod):
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.analysis import jaxpr_rules
    assert not telemetry.enabled()
    jaxpr_rules.begin_census()
    assert jaxpr_rules.trace_census_active() and telemetry.enabled()
    with pytest.raises(RuntimeError):
        jaxpr_rules.begin_census()     # unbalanced arming is loud
    jaxpr_rules.end_census()
    assert not jaxpr_rules.trace_census_active()
    assert not telemetry.enabled()


def test_trace_census_refuses_to_destroy_a_live_registry(jax_mod):
    # arming over an enabled telemetry session would reset (lose) its
    # accumulated counters/sites — refuse loudly instead
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.analysis import jaxpr_rules
    telemetry.enable()
    try:
        with pytest.raises(RuntimeError, match="already enabled"):
            jaxpr_rules.begin_census()
        assert not jaxpr_rules.trace_census_active()
    finally:
        telemetry.disable()
        telemetry.reset()


# ============================== C1-C4: concurrency-lifecycle (ISSUE 15)

from lightgbm_tpu.analysis.concurrency_rules import (ConcurrencyConfig,
                                                     run_concurrency_rules)


def _clint(src, path="fix_c.py", **cfg):
    return run_concurrency_rules(
        {path: textwrap.dedent(src)},
        ConcurrencyConfig(**cfg) if cfg else ConcurrencyConfig(
            hatch_inventory=set()))


C1_BAD_CLASS = """
import threading

class Pump:
    def __init__(self):
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def close(self):
        self._t.join()
"""

C1_BAD_NO_CLOSE = """
import threading

class FireAndForget:
    def __init__(self):
        threading.Thread(target=self._run, daemon=True).start()
"""

C1_OK_CLASS = """
import threading
from lightgbm_tpu import lifecycle

class Pump:
    def __init__(self):
        self._t = threading.Thread(target=self._run, daemon=True)
        lifecycle.track("pump", self, self.close)
        self._t.start()

    def close(self):
        self._t.join()
        lifecycle.untrack(self)
"""

C1_BAD_BARE = """
import threading

def prefetch(it):
    threading.Thread(target=lambda: list(it), daemon=True).start()
"""

C1_OK_BARE = """
import threading
from lightgbm_tpu import lifecycle

def prefetch(it):
    t = threading.Thread(target=lambda: list(it), daemon=True)
    lifecycle.track("prefetch", t, t.join)
    t.start()
"""


def test_c1_fires_on_unregistered_thread_class():
    (f,) = _clint(C1_BAD_CLASS)
    assert f.rule == "C1" and f.line == 6
    assert "lifecycle.track" in f.message and "Pump" in f.message


def test_c1_fires_on_class_without_close_entry_point():
    (f,) = _clint(C1_BAD_NO_CLOSE)
    assert f.rule == "C1" and "close" in f.message


def test_c1_clean_on_registered_class_with_close():
    assert _clint(C1_OK_CLASS) == []


def test_c1_bare_function_spawn_needs_track_in_same_function():
    (f,) = _clint(C1_BAD_BARE)
    assert f.rule == "C1" and f.symbol == "prefetch"
    assert _clint(C1_OK_BARE) == []


C2_BAD = """
def deliver(batch, scores):
    ofs = 0
    for r in batch:
        if not r.future.cancelled():
            r.future.set_result(scores[:, ofs:ofs + r.rows])
        ofs += r.rows
"""

C2_OK = """
def deliver(batch, scores):
    ofs = 0
    for r in batch:
        try:
            if not r.future.cancelled():
                r.future.set_result(scores[:, ofs:ofs + r.rows])
        except Exception:
            pass
        ofs += r.rows

def fail(batch, e):
    for r in batch:
        try:
            r.future.set_exception(e)
        except (RuntimeError, InvalidStateError):
            pass
"""


def test_c2_fires_on_unguarded_future_set():
    # the cancelled() pre-check is NOT enough: the check->set window IS
    # the race (the exact PR 13 ServingFront bug, generalized)
    (f,) = _clint(C2_BAD)
    assert f.rule == "C2" and f.line == 6 and f.site == ".set_result"


def test_c2_clean_when_set_rides_an_absorbing_try():
    assert _clint(C2_OK) == []


C3_BAD = """
import time

class Front:
    def flush(self):
        with self._cond:
            self._cond.wait(0.05)
            self._thread.join()
            time.sleep(0.5)
            data = open(self.path).read()
            self._queue.put(data)
        return data
"""

C3_OK = """
class Front:
    def flush(self):
        with self._cond:
            while self._pending is None and not self._closing:
                self._cond.wait()
            item, self._pending = self._pending, None
            self._cond.notify_all()
        self._io.write(item)
        self._thread.join()

    def drain(self):
        with self._cond:
            self._queue.put(1, timeout=0.1)
            got = self._table.get("key")
"""


def test_c3_fires_on_each_blocking_op_under_the_lock():
    found = _clint(C3_BAD)
    sites = {f.site for f in found}
    assert all(f.rule == "C3" for f in found)
    assert {"self._thread.join", "time.sleep", "open",
            "self._queue.put"} <= sites
    # cv.wait on the lock object itself is exempt (wait RELEASES it)
    assert not any("cond" in s for s in sites)


def test_c3_clean_on_lock_waits_timed_queue_ops_and_outside_io():
    assert _clint(C3_OK) == []


C4_BAD_RAW = """
import os

def no_pallas():
    return os.environ.get("LGBM_TPU_NO_PALLAS", "") == "1"
"""

C4_BAD_ALIAS = """
import os
ENV_VAR = "LGBM_TPU_FAULT_AT"

def spec():
    return os.environ.get(ENV_VAR)
"""

C4_BAD_UNREGISTERED = """
from lightgbm_tpu import hatches

def ghost():
    return hatches.flag("LGBM_TPU_GHOST")
"""

C4_OK = """
from lightgbm_tpu import hatches

def no_pallas():
    return hatches.flag("LGBM_TPU_NO_PALLAS")
"""


def test_c4_fires_on_raw_env_read():
    (f,) = _clint(C4_BAD_RAW)
    assert f.rule == "C4" and f.site == "LGBM_TPU_NO_PALLAS"
    assert f.line == 5


def test_c4_resolves_module_constant_aliases():
    (f,) = _clint(C4_BAD_ALIAS)
    assert f.rule == "C4" and f.site == "LGBM_TPU_FAULT_AT"


def test_c4_fires_on_helper_read_missing_from_inventory():
    (f,) = _clint(C4_BAD_UNREGISTERED,
                  hatch_inventory={"LGBM_TPU_NO_PALLAS"})
    assert f.rule == "C4" and f.site == "LGBM_TPU_GHOST"
    assert "inventory" in f.message


def test_c4_clean_on_registered_helper_read():
    assert _clint(C4_OK, hatch_inventory={"LGBM_TPU_NO_PALLAS"}) == []


def test_hatches_helper_loud_rejects(monkeypatch):
    """The runtime half of C4: a typo'd hatch VALUE must reject, not
    silently do nothing."""
    from lightgbm_tpu import hatches
    from lightgbm_tpu.utils import log
    monkeypatch.setenv("LGBM_TPU_NO_PALLAS", "true")
    with pytest.raises(log.LightGBMError):
        hatches.flag("LGBM_TPU_NO_PALLAS")
    monkeypatch.setenv("LGBM_TPU_NO_PALLAS", "1")
    assert hatches.flag("LGBM_TPU_NO_PALLAS") is True
    monkeypatch.delenv("LGBM_TPU_NO_PALLAS")
    assert hatches.flag("LGBM_TPU_NO_PALLAS") is False
    with pytest.raises(log.LightGBMError):
        hatches.flag("LGBM_TPU_UNREGISTERED_GHOST")


# =============================== D1-D3: cross-artifact drift (ISSUE 15)

from lightgbm_tpu.analysis import drift_rules


D1_FILES_OK = {
    "pkg/serving.py": textwrap.dedent("""
        from . import telemetry
        def go(n):
            telemetry.count("serve/rows", n)
            telemetry.count(f"serve/bucket_{n}")
            with telemetry.span("predict"):
                telemetry.record_collective("serve/tree_psum", "psum",
                                            "tree", 4)
    """),
}
D1_INV_OK = {
    "counter": ("serve/rows", "serve/bucket_*"),
    "span": ("predict",),
    "wire": ("serve/tree_psum",),
    "dynamic": (),
}


def test_d1_clean_when_census_matches_inventory():
    assert drift_rules.check_telemetry_inventory(
        D1_FILES_OK, D1_INV_OK, telemetry_path="pkg/telemetry.py") == []


def test_d1_fires_on_undocumented_usage():
    # deleting a documented family line makes the census fire — the
    # acceptance-criteria liveness direction
    inv = dict(D1_INV_OK, counter=("serve/bucket_*",))
    found = drift_rules.check_telemetry_inventory(
        D1_FILES_OK, inv, telemetry_path="pkg/telemetry.py")
    assert any(f.rule == "D1" and f.site == "serve/rows"
               and f.path == "pkg/serving.py" and f.line == 4
               for f in found)


def test_d1_fires_on_stale_documentation():
    inv = dict(D1_INV_OK, span=("predict", "ghost_span"))
    found = drift_rules.check_telemetry_inventory(
        D1_FILES_OK, inv, telemetry_path="pkg/telemetry.py")
    assert any(f.rule == "D1" and f.site == "ghost_span"
               and "stale" in f.message for f in found)


def test_d1_real_inventory_census_is_live():
    """Acceptance: deleting any one STATIC documented telemetry family
    line from the real inventory makes the census (and therefore
    ``--check``) flag it."""
    from lightgbm_tpu import telemetry
    files = {p: open(p).read()
             for p in glob.glob(os.path.join(
                 REPO, "lightgbm_tpu", "**", "*.py"), recursive=True)}
    tel_path = next(p for p in files if p.endswith("telemetry.py"))
    for dropped in ("serve/swaps", "ckpt/written"):
        inv = {
            "counter": tuple(n for n in telemetry.COUNTER_FAMILIES
                             if n != dropped),
            "span": telemetry.SPAN_FAMILIES,
            "wire": telemetry.WIRE_SITE_FAMILIES,
            "dynamic": telemetry.DYNAMIC_WIRE_SITES,
        }
        found = drift_rules.check_telemetry_inventory(
            files, inv, telemetry_path=tel_path)
        assert any(f.rule == "D1" and f.site == dropped
                   for f in found), dropped


D2_GATES_OK = {
    "RATE_KEYS": (("value", "spread"), ("x_rows_per_sec", "x_spread")),
    "LATENCY_KEYS": (("x_p99_us", "x_spread"),),
    "ABSOLUTE_ZERO_KEYS": (("x_recompiles", "d"),),
    "ABSOLUTE_TRUE_KEYS": (("x_restore_exact", "d"),),
    "_source": "",
}
D2_BENCH_OK = ('out = {"value": 1, "spread": 0, "x_rows_per_sec": 2,'
               ' "x_spread": 0, "x_p99_us": 3, "x_recompiles": 0,'
               ' "x_restore_exact": True}')


def test_d2_clean_when_gates_cover_emissions():
    assert drift_rules.check_perf_gate_coverage(
        D2_GATES_OK, D2_BENCH_OK, informational={}) == []


def test_d2_fires_on_stale_gate_key():
    gates = dict(D2_GATES_OK,
                 RATE_KEYS=D2_GATES_OK["RATE_KEYS"]
                 + (("ghost_rows_per_sec", "ghost_spread"),))
    found = drift_rules.check_perf_gate_coverage(gates, D2_BENCH_OK,
                                                 informational={})
    assert {f.site for f in found} == {"ghost_rows_per_sec",
                                       "ghost_spread"}
    assert all("gates nothing" in f.message for f in found)


def test_d2_fires_on_ungated_emission():
    # deleting a gate key whose lane bench still emits — the acceptance
    # liveness direction
    gates = dict(D2_GATES_OK, RATE_KEYS=(("value", "spread"),),
                 LATENCY_KEYS=())
    found = drift_rules.check_perf_gate_coverage(gates, D2_BENCH_OK,
                                                 informational={})
    sites = {f.site for f in found}
    assert {"x_rows_per_sec", "x_spread", "x_p99_us"} <= sites


def test_d2_real_gate_census_is_live():
    """Acceptance: deleting any one perf_gate key pair while bench.py
    still emits the lane makes the census flag it."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_pg_test", os.path.join(REPO, "scripts", "perf_gate.py"))
    pg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pg)
    bench_src = open(os.path.join(REPO, "bench.py")).read()
    gates = {
        "RATE_KEYS": tuple(p for p in pg.RATE_KEYS
                           if p[0] != "ingest_rows_per_sec"),
        "LATENCY_KEYS": pg.LATENCY_KEYS,
        "ABSOLUTE_ZERO_KEYS": pg.ABSOLUTE_ZERO_KEYS,
        "ABSOLUTE_TRUE_KEYS": pg.ABSOLUTE_TRUE_KEYS,
        "_source": "",
    }
    found = drift_rules.check_perf_gate_coverage(gates, bench_src)
    assert any(f.rule == "D2" and f.site == "ingest_rows_per_sec"
               for f in found)


D3_CONFIG_OK = """
import dataclasses
from .utils import log

@dataclasses.dataclass
class IOConfig:
    max_bin: int = 256
    mode: str = "auto"

    def set(self, params):
        self.max_bin = _get_int(params, "max_bin", self.max_bin)
        if "mode" in params:
            value = params["mode"].lower()
            log.check(value in ("auto", "x"), "mode must be auto or x")
            self.mode = value
"""

D3_CLI_OK = """
KNOB_INVENTORY = {
    "max_bin": "max bins per feature",
    "mode": "auto or x",
}
"""


def test_d3_clean_on_matching_inventory():
    assert drift_rules.check_knob_inventory(
        textwrap.dedent(D3_CONFIG_OK), textwrap.dedent(D3_CLI_OK),
        freeform={}, internal={}) == []


def test_d3_fires_on_undocumented_knob_and_stale_entry():
    cli = 'KNOB_INVENTORY = {"max_bin": "x", "ghost_knob": "gone"}'
    found = drift_rules.check_knob_inventory(
        textwrap.dedent(D3_CONFIG_OK), cli, freeform={}, internal={})
    sites = {(f.site, f.symbol) for f in found}
    assert ("mode", "set") in sites          # undocumented knob
    assert ("ghost_knob", "cli") in sites    # stale inventory entry


def test_d3_fires_on_unvalidated_knob_and_unreachable_field():
    src = """
import dataclasses

@dataclasses.dataclass
class IOConfig:
    path: str = ""
    orphan: int = 0

    def set(self, params):
        self.path = _get_str(params, "path", self.path)
"""
    cli = 'KNOB_INVENTORY = {"path": "a path"}'
    found = drift_rules.check_knob_inventory(
        textwrap.dedent(src), cli, freeform={}, internal={})
    assert any(f.site == "path" and "silently" in f.message
               for f in found)
    assert any(f.site == "orphan" and "unreachable" in f.message
               for f in found)
    # the same free-form knob with a written justification passes
    found2 = drift_rules.check_knob_inventory(
        textwrap.dedent(src), cli,
        freeform={"path": "output path; open() surfaces failures"},
        internal={"orphan": "derived"})
    assert found2 == []


# ==================== tier-1 gates: layers 3a/3b clean on the tree

def test_concurrency_layer_clean_on_shipped_tree():
    """The tier-1 C-rule gate: zero findings over the whole package
    against the committed (empty) baseline — the in-suite mirror of
    ``python scripts/graftlint.py --concurrency-only``."""
    baseline = Baseline.load(default_baseline_path())
    findings, _sup = split_baseline(
        gl_driver.run_concurrency_layer(), baseline)
    assert findings == [], "\n".join(f.format() for f in findings)


def test_drift_layer_clean_on_shipped_tree():
    """The tier-1 D-rule gate: the telemetry inventory, perf_gate key
    coverage and CLI knob inventory all census clean."""
    baseline = Baseline.load(default_baseline_path())
    findings, _sup = split_baseline(gl_driver.run_drift_layer(), baseline)
    assert findings == [], "\n".join(f.format() for f in findings)


def test_graftlint_script_all_four_layers_exit_zero():
    """ISSUE 15 acceptance: ``scripts/graftlint.py --check`` exits 0
    over ast+jaxpr+concurrency+drift with the EMPTY committed
    baseline."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "graftlint.py"),
         "--check"],
        capture_output=True, text=True, cwd=REPO, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ast+jaxpr+concurrency+drift" in r.stdout


def test_stale_baseline_reported_for_new_rule_ids(tmp_path):
    """The stale-suppression finding covers the C/D ids too: an entry
    naming a C1/D2 site that matches nothing must flag."""
    bad = tmp_path / "stale_cd.json"
    bad.write_text(json.dumps({"version": 1, "suppressions": [
        {"rule": "C1", "path": "nowhere.py", "symbol": "ghost",
         "justification": "obsolete"},
        {"rule": "D2", "path": "bench.py", "symbol": "bench",
         "site": "ghost_rows_per_sec", "justification": "obsolete"}]}))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "graftlint.py"),
         "--concurrency-only", "--drift-only", "--baseline", str(bad)],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert r.returncode == 1, r.stdout + r.stderr
    assert r.stdout.count("STALE BASELINE") == 2


# ================== shared lifecycle inventory (ISSUE 15 satellite)

def test_lifecycle_tracks_and_reports_leaks():
    from lightgbm_tpu import lifecycle

    class Obj:
        closed = False

        def close(self):
            self.closed = True
            lifecycle.untrack(self)

    o = Obj()
    lifecycle.track("test-kind", o, o.close)
    assert lifecycle.live_count("test-kind") == 1
    assert any(k == "test-kind" for k, _n, _c in lifecycle.leaks())
    o.close()
    assert lifecycle.live_count("test-kind") == 0
    lifecycle.untrack(o)                      # idempotent


def test_lifecycle_sees_leaked_checkpoint_writer(tmp_path):
    """The conftest guard's new single read: a CheckpointWriter left
    open appears in lifecycle.leaks() under its kind, and its closer
    reaps it."""
    from lightgbm_tpu import checkpoint as ckpt
    from lightgbm_tpu import lifecycle
    w = ckpt.CheckpointWriter(str(tmp_path))
    assert ckpt.live_writers() == 1
    leak = [e for e in lifecycle.leaks() if e[0] == ckpt.WRITER_KIND]
    assert len(leak) == 1
    leak[0][2]()                              # the guard's cleanup path
    assert ckpt.live_writers() == 0 and not w.alive


def test_lifecycle_sees_armed_fault_probe(monkeypatch):
    from lightgbm_tpu import faults, lifecycle
    faults.arm(3, "stall")
    try:
        assert any(k == "fault-hatch" for k, _n, _c in lifecycle.leaks())
    finally:
        faults.clear()
    assert not any(k == "fault-hatch" for k, _n, _c in lifecycle.leaks())


def test_prefetch_thread_registers_and_deregisters():
    from lightgbm_tpu import lifecycle
    from lightgbm_tpu.io import parser

    gen = parser.prefetch_chunks(iter([[1], [2], [3]]))
    assert next(gen) == [1]
    # early drop: the generator's finally must stop AND deregister
    gen.close()
    assert lifecycle.live_count("prefetch") == 0
    # full drain deregisters too
    assert list(parser.prefetch_chunks(iter([[4], [5]]))) == [[4], [5]]
    assert lifecycle.live_count("prefetch") == 0


# ================================== baseline / suppression mechanics

def test_baseline_suppresses_and_reports_stale(tmp_path):
    f = Finding("R1", "lightgbm_tpu/foo.py", 10, "fn", "lax.psum", "m")
    base = Baseline([
        {"rule": "R1", "path": "foo.py", "symbol": "fn",
         "site": "lax.psum", "justification": "measured, deliberate"},
        {"rule": "R3", "path": "gone.py", "symbol": "x",
         "justification": "stale"},
    ])
    kept, suppressed = split_baseline([f], base)
    assert kept == [] and suppressed == [f]
    assert [e["path"] for e in base.stale_entries()] == ["gone.py"]
    p = tmp_path / "b.json"
    base.save(str(p))
    loaded = Baseline.load(str(p))
    assert len(loaded.entries) == 2


def test_baseline_rejects_entries_without_justification(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"suppressions": [
        {"rule": "R1", "path": "x.py", "symbol": "f"}]}))
    with pytest.raises(ValueError):
        Baseline.load(str(p))


def test_rule_catalog_covers_every_rule_id():
    assert set(RULES) == {"R1", "R2", "R3", "R4", "J1", "J2",
                          "C1", "C2", "C3", "C4", "D1", "D2", "D3"}
    for title, hint in RULES.values():
        assert title and hint


# ====================================== tier-1 gate: the clean tree

def test_ast_layer_clean_on_shipped_tree():
    """The tier-1 AST gate: zero findings over the whole package against
    the committed baseline — the in-suite mirror of
    ``python scripts/graftlint.py --ast-only``."""
    baseline = Baseline.load(default_baseline_path())
    findings, _sup = split_baseline(gl_driver.run_ast_layer(), baseline)
    assert findings == [], "\n".join(f.format() for f in findings)
    assert baseline.stale_entries() == []


def test_jaxpr_layer_clean_on_canonical_programs():
    """The tier-1 jaxpr gate: J1+J2 clean over the canonical small-schema
    programs (serial policies, int8 exchange, serving BFS, (2,2)-mesh
    learners).  Traces are cached per session (driver lru_cache), so the
    census cross-check below reuses this pass."""
    findings = gl_driver.run_jaxpr_layer()
    assert findings == [], "\n".join(f.format() for f in findings)


# ================== ISSUE 10 acceptance: census vs wire-site inventory

@pytest.fixture(scope="module")
def mesh22_traces():
    from lightgbm_tpu.analysis.programs import (parallel_grow_program,
                                                trace_program)
    out = {}
    for tl in ("data", "hybrid", "voting"):
        prog = parallel_grow_program(tl)
        out[tl] = trace_program(prog)
    return out


# the PR 9 seam inventory per learner on the (2,2) mesh — the same site
# names __graft_entry__._wire_smoke records into MULTICHIP_WIRE
EXPECTED_SITES = {
    "data": {"dp_psum/leafwise/hist_allreduce",
             "dp_psum/leafwise/root_hist",
             "dp_psum/leafwise/root_stats"},
    "hybrid": {"hybrid/leafwise/hist_allreduce",
               "hybrid/leafwise/root_hist",
               "hybrid/leafwise/root_stats",
               "hybrid/leafwise/splitinfo_allreduce"},
    "voting": {"voting/leafwise/votes_allgather",
               "voting/leafwise/voted_hist_allreduce",
               "voting/leafwise/splitinfo_allreduce",
               "voting/leafwise/root_votes_allgather",
               "voting/leafwise/root_voted_hist_allreduce",
               "voting/leafwise/root_splitinfo_allreduce",
               "voting/leafwise/root_stats"},
}


def test_census_agrees_with_wire_site_inventory(mesh22_traces):
    """J2 on the (2,2)-mesh dryrun programs: what XLA will execute (the
    jaxpr collective eqns) agrees with the declared wire-site inventory
    the gated MULTICHIP_WIRE model prices — per kind, presence matches
    exactly and eqns >= declared traced calls (one record may cover the
    several eqns of a tree-mapped allreduce)."""
    from lightgbm_tpu.analysis.jaxpr_rules import (check_collective_census,
                                                   collective_census,
                                                   declared_census)
    for tl, (jaxpr, sites) in mesh22_traces.items():
        assert check_collective_census("grow/%s" % tl, jaxpr, sites) == []
        assert set(sites) == EXPECTED_SITES[tl], tl
        actual = collective_census(jaxpr)
        declared = declared_census(sites)
        assert set(actual) == set(declared), tl
        for kind, n in declared.items():
            assert actual[kind] >= n, (tl, kind, dict(actual),
                                       dict(declared))


def test_census_matches_recorded_multichip_wire_rows():
    """Cross-check against the RECORDED MULTICHIP trajectory: wherever a
    MULTICHIP_r*.json round carries a MULTICHIP_WIRE line (PR 9 onward),
    its per-learner site names must be a superset of the canonical grow
    programs' declared inventory — the gated wire-byte model and the
    census can never silently diverge.  Rounds without the line (r01-r05
    predate the smoke) are skipped by design."""
    import re
    rows = []
    for path in sorted(glob.glob(os.path.join(REPO, "MULTICHIP_r*.json"))):
        with open(path) as fh:
            rec = json.load(fh)
        m = re.search(r"MULTICHIP_WIRE (\{.*\})", rec.get("tail", "") or "")
        if m:
            rows.append((path, json.loads(m.group(1))))
    if not rows:
        pytest.skip("no recorded MULTICHIP_WIRE rounds yet (pre-PR 9 "
                    "history)")
    for path, wire in rows:
        for tl, expected in EXPECTED_SITES.items():
            recorded = set(wire.get("sites", {}).get(tl, {}))
            assert expected <= recorded, (path, tl,
                                          expected - recorded)


# ======================================== driver script exit contract

def test_graftlint_script_ast_only_exits_zero():
    """``scripts/graftlint.py --ast-only`` on the shipped tree: exit 0,
    no JAX needed (layer-1 contract)."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "graftlint.py"),
         "--ast-only"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "clean" in r.stdout


def test_graftlint_script_flags_stale_baseline(tmp_path):
    """Exit 1 with a pointed finding when the baseline holds a
    suppression that matches nothing (stale entries may only be removed
    consciously)."""
    bad = tmp_path / "stale.json"
    bad.write_text(json.dumps({"version": 1, "suppressions": [
        {"rule": "R1", "path": "nowhere.py", "symbol": "ghost",
         "justification": "obsolete"}]}))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "graftlint.py"),
         "--ast-only", "--baseline", str(bad)],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "STALE BASELINE" in r.stdout


def test_graftlint_script_explain_allowlist():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "graftlint.py"),
         "--explain-allowlist"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr


# ==================================== compat-shim surface stays shrunk

SHIM_SURFACES = {
    "lightgbm_tpu.models.grower": {
        "build_histogram", "grow_tree", "grow_tree_impl",
        "grow_tree_unified", "SeamSchedule"},
    "lightgbm_tpu.models.grower_depthwise": {
        "histogram_leafbatch", "grow_tree_depthwise",
        "grow_tree_depthwise_jit", "grow_tree_unified", "num_levels",
        "SeamSchedule"},
    "lightgbm_tpu.models.grower_leafcompact": {
        "build_histogram", "grow_tree_leafcompact",
        "grow_tree_leafcompact_impl", "grow_tree_unified", "SeamSchedule"},
}


def test_shim_surface_is_exactly_the_documented_set():
    """The ~50-line compat shims keep ONLY the documented keyword-seam
    entry points and patchable histogram attributes (ISSUE 10 satellite:
    the dead re-exports the AST pass proved unreachable stay deleted)."""
    import importlib
    for modname, expected in SHIM_SURFACES.items():
        mod = importlib.import_module(modname)
        public = {n for n in vars(mod)
                  if not n.startswith("_") and n not in ("annotations",)
                  and not isinstance(vars(mod)[n], type(os))}
        assert public == expected, (modname, public ^ expected)


def test_shim_annotations_resolve():
    """No dangling names in shim signatures: every annotation must
    resolve against the shrunk module namespace (get_type_hints is what
    doc/typing tooling runs)."""
    import typing
    from lightgbm_tpu.models import (grower, grower_depthwise,
                                     grower_leafcompact)
    for fn in (grower.grow_tree_impl,
               grower_depthwise.grow_tree_depthwise,
               grower_leafcompact.grow_tree_leafcompact_impl):
        typing.get_type_hints(fn)


# ============================ the suite's own rule: no test holds the clock

def test_a_test_past_the_time_limit_is_failed_by_name(tmp_path):
    """tests/conftest.py's limit, loaded into a pytest of its own with
    the limit set to 2 s and the grace after it to 2 s: the test that
    sleeps is failed under its name and the one after it still runs;
    the test the alarm cannot reach has its stack written to the log."""
    (tmp_path / "conftest.py").write_text(textwrap.dedent("""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "suite_conftest", %r)
        suite = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(suite)
        suite.TEST_LIMIT_S = suite.TEST_LIMIT_GRACE_S = 2
        _test_time_limit = suite._test_time_limit
        pytest_configure = suite.pytest_configure
    """ % os.path.join(REPO, "tests", "conftest.py")))
    (tmp_path / "test_sleeps.py").write_text(textwrap.dedent("""
        import signal, time
        def test_sleeps_past_the_limit():
            time.sleep(60)
        def test_after_it():
            pass
        def test_out_of_the_alarms_reach():
            signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
            try:
                time.sleep(6)
            finally:
                signal.pthread_sigmask(signal.SIG_UNBLOCK,
                                       {signal.SIGALRM})
    """))
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", str(tmp_path)],
        capture_output=True, text=True, cwd=str(tmp_path), timeout=120,
        env=dict(os.environ, PYTHONPATH=REPO))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "2 failed, 1 passed" in r.stdout, r.stdout
    for name in ("test_sleeps_past_the_limit",
                 "test_out_of_the_alarms_reach"):
        assert ("test_sleeps.py::%s ran past the 2 s" % name
                in r.stdout), r.stdout
    assert "Timeout (0:00:04)!" in r.stderr, r.stderr
    assert "in test_out_of_the_alarms_reach" in r.stderr, r.stderr
