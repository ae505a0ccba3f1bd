"""quiplint passes + the runtime lock-order sanitizer (docs/analysis.md).

Three layers of coverage:

* **synthetic fixtures** — every lint pass both *flags* a minimal
  violation and *accepts* the sanctioned spellings (with-blocks,
  ``# requires:`` contracts, ``# unguarded:`` waivers, impl forwarding);
* **real-tree checks** — ``lint_repo()`` is clean on the shipped tree
  (the CI gate), and stays *sensitive*: perturbing the real sources
  (dropping a contract, renaming a lock, orphaning a span) re-introduces
  findings, so a green lint run means the passes are actually looking;
* **sanitizer** — a scripted 3-thread A→B / B→C / C→A inversion is
  reported as a potential deadlock (with the JSON artifact written),
  while consistent orderings, same-name key locks, reentrancy, and
  Condition ``wait()`` stay acyclic with honest held-sets.

Plus numpy/ref agreement smokes for the kernel paths the parity pass
pins (``bloom_probe`` / ``hash_join_match`` / ``masked_distance``).
"""

from __future__ import annotations

import json
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import lint, lockcheck
from repro.analysis.lint import PASSES, lint_repo, lint_sources
from repro.kernels import ops
from repro.kernels.hashing import fold64


def _msgs(findings):
    return [str(f) for f in findings]


# --------------------------------------------------------------------------- #
# env-discipline
# --------------------------------------------------------------------------- #
def test_env_pass_flags_direct_reads():
    src = (
        "import os\n"
        'a = os.environ["QUIP_TRACE"]\n'
        'b = os.environ.get("QUIP_TRACE")\n'
        'c = os.getenv("QUIP_TRACE")\n'
    )
    f = PASSES["env-discipline"]({"service/x.py": src})
    assert len(f) == 3, _msgs(f)
    assert all("QUIP_TRACE" in x.message for x in f)


def test_env_pass_flags_mutation_outside_launch_whitelist():
    src = 'import os\nos.environ["XLA_FLAGS"] = "x"\n'
    f = PASSES["env-discipline"]({"service/x.py": src})
    assert len(f) == 1 and "mutation" in f[0].message
    # core/env.py is the only whitelisted file; any other path is flagged
    assert PASSES["env-discipline"]({"core/env.py": src}) == []
    f = PASSES["env-discipline"]({"launch/dryrun.py": src})
    assert len(f) == 1 and "mutation" in f[0].message


def test_env_pass_flags_unregistered_knob():
    src = 'from repro.core.env import env_flag\nv = env_flag("QUIP_NOPE")\n'
    f = PASSES["env-discipline"]({"core/x.py": src})
    assert any("ENV_REGISTRY" in x.message for x in f)
    assert any("not a registered knob" in x.message for x in f)
    ok = 'from repro.core.env import env_flag\nv = env_flag("QUIP_TRACE")\n'
    assert PASSES["env-discipline"]({"core/x.py": ok}) == []


# --------------------------------------------------------------------------- #
# counter-discipline
# --------------------------------------------------------------------------- #
def test_counters_pass_flags_unknown_field():
    src = "def f(self):\n    self.counters.bogus_total += 1\n"
    f = PASSES["counter-discipline"]({"core/x.py": src})
    assert len(f) == 1 and "bogus_total" in f[0].message
    ok = "def f(self):\n    self.counters.join_tests += 1\n"
    assert PASSES["counter-discipline"]({"core/x.py": ok}) == []


def test_counters_pass_requires_provenance_mirror():
    bad = "def f(self):\n    self.counters.imputations += 3\n"
    f = PASSES["counter-discipline"]({"imputers/x.py": bad})
    assert len(f) == 1 and "on_flush" in f[0].message
    ok = (
        "def f(self):\n"
        "    self.counters.imputations += 3\n"
        "    self.provenance.on_flush(self, [], [], 0)\n"
    )
    assert PASSES["counter-discipline"]({"imputers/x.py": ok}) == []


# --------------------------------------------------------------------------- #
# lock-discipline
# --------------------------------------------------------------------------- #
_LOCK_FIXTURE = '''
class C:
    def __init__(self):
        self._q = []       # guarded-by: _lock
        self._n = 0        # guarded-by: _lock|_cv
        self._lock = object()

    def bad_mutator(self):
        self._q.append(1)

    def bad_subscript(self):
        self._q[0] = 2

    def good_with(self):
        with self._lock:
            self._q.append(1)
            del self._q[0]

    def good_alternative(self):
        with self._cv:
            self._n += 1

    def good_contract(self):  # requires: _lock
        self._q.append(2)

    def good_waiver(self):
        self._n = 5  # unguarded: test fixture waiver
'''


def test_locks_pass_fixture():
    f = PASSES["lock-discipline"]({"service/x.py": _LOCK_FIXTURE})
    lines = sorted(x.line for x in f)
    # exactly the two bad_* mutations; every sanctioned spelling accepted
    assert len(f) == 2, _msgs(f)
    assert all("guarded-by" in x.message for x in f)
    bad1 = _LOCK_FIXTURE.splitlines().index("        self._q.append(1)") + 1
    assert lines[0] == bad1


# --------------------------------------------------------------------------- #
# span-discipline
# --------------------------------------------------------------------------- #
def test_spans_pass_fixture():
    bad = (
        "def f(tracer):\n"
        '    tracer.span("x")\n'
        '    tracer.begin("y")\n'
    )
    f = PASSES["span-discipline"]({"obs/x.py": bad})
    # orphan span + discarded begin + module begins-without-end
    assert len(f) == 3, _msgs(f)
    ok = (
        "def f(tracer):\n"
        '    with tracer.span("x"):\n'
        "        pass\n"
        '    sp = tracer.span("y")\n'
        "    with sp:\n"
        "        pass\n"
        '    tid = tracer.begin("z")\n'
        "    tracer.end(tid)\n"
        "def g(tracer):\n"
        '    return tracer.span("caller-owned")\n'
    )
    assert PASSES["span-discipline"]({"obs/x.py": ok}) == []


# --------------------------------------------------------------------------- #
# kernel-parity
# --------------------------------------------------------------------------- #
_OPS_FIXTURE = '''
__all__ = ["op_full", "op_half", "op_bare", "op_forward", "resolve_t_impl"]

def resolve_t_impl(impl=None):
    return impl or env_choice("QUIP_TRACE", ("numpy", "ref", "pallas"), "numpy")

def op_full(x, impl=None):
    impl = resolve_t_impl(impl)
    if impl == "numpy":
        return x
    if impl == "pallas":
        return x
    return x

def op_half(x, impl=None):
    impl = resolve_t_impl(impl)
    if impl == "numpy":
        return x
    return x

def op_bare(x, impl=None):
    return x

def op_forward(x, impl=None):
    return op_full(x, impl=impl)
'''


def test_parity_pass_fixture():
    f = PASSES["kernel-parity"]({"kernels/ops.py": _OPS_FIXTURE})
    by_op = {x.message.split(" ")[1]: x.message for x in f}
    assert set(by_op) == {"op_half", "op_bare"}, _msgs(f)
    assert "'pallas'" in by_op["op_half"]
    assert "neither resolves" in by_op["op_bare"]
    # the pass only looks at kernels/ops.py
    assert PASSES["kernel-parity"]({"kernels/other.py": _OPS_FIXTURE}) == []


# --------------------------------------------------------------------------- #
# the real tree: clean, and the passes stay sensitive to perturbations
# --------------------------------------------------------------------------- #
def test_repo_lint_is_clean():
    assert lint_repo() == []


def _real_sources():
    return lint.load_sources(lint.find_repo_root())


def _perturb(sources, path, old, new):
    assert old in sources[path], f"perturbation anchor gone from {path}: {old!r}"
    sources[path] = sources[path].replace(old, new)
    return sources


def test_perturb_dropped_requires_contract_is_flagged():
    srcs = _perturb(_real_sources(), "imputers/base.py",
                    "# requires: flush_lock", "")
    f = [x for x in PASSES["lock-discipline"](srcs)
         if x.path == "imputers/base.py"]
    assert f and all("guarded-by" in x.message for x in f)


def test_perturb_renamed_lock_is_flagged():
    srcs = _perturb(_real_sources(), "obs/trace.py",
                    "with self._lock:", "with self._nolock:")
    f = [x for x in PASSES["lock-discipline"](srcs)
         if x.path == "obs/trace.py"]
    assert f, "tracer mutations outside the renamed lock were not flagged"


def test_perturb_orphaned_begin_is_flagged():
    srcs = _perturb(_real_sources(), "service/server.py",
                    "self.tracer.end(", "self.tracer.noop(")
    f = [x for x in PASSES["span-discipline"](srcs)
         if x.path == "service/server.py"]
    assert any("never tracer.end" in x.message for x in f)


def test_perturb_removed_waiver_is_flagged():
    srcs = _perturb(
        _real_sources(), "service/server.py",
        "  # unguarded: workers joined; no concurrent readers remain", "")
    f = [x for x in PASSES["lock-discipline"](srcs)
         if x.path == "service/server.py"]
    assert any("_pool" in x.message for x in f)


def test_lint_sources_reports_syntax_errors():
    f = lint_sources({"core/x.py": "def broken(:\n"})
    assert f and all("syntax error" in x.message for x in f)


def test_env_docs_render_roundtrip():
    text = ("head\n" + lint.DOCS_BEGIN + "\nstale\n" + lint.DOCS_END
            + "\ntail\n")
    rendered = lint.render_env_docs(text)
    assert lint.env_registry_table() in rendered
    assert lint.render_env_docs(rendered) == rendered  # idempotent
    assert lint.render_env_docs("no markers") is None


# --------------------------------------------------------------------------- #
# lock-order sanitizer
# --------------------------------------------------------------------------- #
@pytest.fixture
def sanitized(monkeypatch):
    monkeypatch.setenv("QUIP_SANITIZE", "locks")
    lockcheck.reset()
    yield
    lockcheck.reset()


def test_factories_plain_when_off(monkeypatch):
    monkeypatch.delenv("QUIP_SANITIZE", raising=False)
    assert type(lockcheck.make_lock("T.x")) is type(threading.Lock())
    assert type(lockcheck.make_rlock("T.x")) is type(threading.RLock())
    monkeypatch.setenv("QUIP_SANITIZE", "garbage")
    with pytest.raises(ValueError):
        lockcheck.make_lock("T.x")


@pytest.mark.timeout(30)
def test_three_thread_cycle_is_potential_deadlock(sanitized, tmp_path):
    a = lockcheck.make_lock("T.A")
    b = lockcheck.make_lock("T.B")
    c = lockcheck.make_lock("T.C")

    def order(first, second):
        with first:
            with second:
                pass

    # three threads, run to completion one after another: no interleaving
    # ever deadlocks, but the acquisition orders close the cycle A→B→C→A
    for pair in ((a, b), (b, c), (c, a)):
        t = threading.Thread(target=order, args=pair)
        t.start()
        t.join()

    rep = lockcheck.report()
    assert rep["cycles"], "cycle not detected from edge set"
    assert rep["potential_deadlocks"], "online detection missed the cycle"
    cyc = rep["potential_deadlocks"][0]
    assert len(cyc["edges"]) >= 2  # both sides of the inversion, with stacks
    assert all(e["stack"] for e in cyc["edges"])

    artifact = tmp_path / "lock_report.json"
    with pytest.raises(AssertionError, match="potential deadlock"):
        lockcheck.assert_acyclic(str(artifact))
    written = json.loads(artifact.read_text())
    assert written["cycles"] and written["mode"] == "locks"


@pytest.mark.timeout(30)
def test_consistent_order_stays_acyclic(sanitized):
    a = lockcheck.make_lock("T.A")
    b = lockcheck.make_lock("T.B")

    def ab():
        with a:
            with b:
                pass

    for _ in range(3):
        t = threading.Thread(target=ab)
        t.start()
        t.join()
    rep = lockcheck.assert_acyclic(artifact_path=None)
    edge = next(e for e in rep["edges"]
                if e["src"] == "T.A" and e["dst"] == "T.B")
    assert edge["count"] == 3  # stack captured once, count accumulated
    assert rep["locks"]["T.A"]["acquisitions"] == 3


def test_same_name_instances_share_a_node_without_self_edges(sanitized):
    k1 = lockcheck.make_lock("T.key")
    k2 = lockcheck.make_lock("T.key")
    with k1:
        with k2:
            pass
    rep = lockcheck.assert_acyclic(artifact_path=None)
    assert all(e["src"] != e["dst"] for e in rep["edges"])
    assert rep["locks"]["T.key"]["acquisitions"] == 2


def test_rlock_reentrancy_orders_only_at_outermost(sanitized):
    rl = lockcheck.make_rlock("T.R")
    other = lockcheck.make_lock("T.O")
    with rl:
        with rl:  # reentrant: no self-edge, depth bookkeeping only
            with other:
                pass
    rep = lockcheck.assert_acyclic(artifact_path=None)
    assert [  # one edge, from the rlock's 0→1 acquisition
        (e["src"], e["dst"]) for e in rep["edges"]
    ] == [("T.R", "T.O")]
    assert rep["locks"]["T.R"]["acquisitions"] == 1


def test_nonblocking_contention_recorded(sanitized):
    lk = lockcheck.make_lock("T.cont")
    got = []
    with lk:
        t = threading.Thread(
            target=lambda: got.append(lk.acquire(blocking=False)))
        t.start()
        t.join()
    assert got == [False]
    rep = lockcheck.report()
    assert rep["locks"]["T.cont"]["contended"] == 1


@pytest.mark.timeout(30)
def test_condition_wait_keeps_held_set_honest(sanitized):
    rl = lockcheck.make_rlock("T.cv_lock")
    cv = lockcheck.make_condition(rl)
    other = lockcheck.make_lock("T.other")
    ready = threading.Event()
    done = []

    def waiter():
        with cv:
            ready.set()
            cv.wait(timeout=10)
            # wait() released and reacquired through the graph: the only
            # edge the next acquire records is cv_lock→other, and the
            # held set is empty again once the with-block exits
            with other:
                done.append(True)

    t = threading.Thread(target=waiter)
    t.start()
    assert ready.wait(10)
    with cv:
        cv.notify_all()
    t.join(10)
    assert done == [True]
    rep = lockcheck.assert_acyclic(artifact_path=None)
    assert any(e["src"] == "T.cv_lock" and e["dst"] == "T.other"
               for e in rep["edges"])


# --------------------------------------------------------------------------- #
# numpy members of the kernel triples (the parity pass pins these exist)
# --------------------------------------------------------------------------- #
def test_bloom_probe_numpy_matches_ref():
    rng = np.random.default_rng(7)
    log2m, num_hashes = 14, 4
    bits = rng.integers(0, 2**32, (1 << log2m) // 32, dtype=np.uint32)
    keys = rng.integers(-(2**62), 2**62, 512).astype(np.int64)
    folded = fold64(keys)
    ref = np.asarray(ops.bloom_probe(
        jnp.asarray(bits), jnp.asarray(folded),
        num_hashes=num_hashes, log2m=log2m, impl="ref"))
    host = np.asarray(ops.bloom_probe(
        bits, folded, num_hashes=num_hashes, log2m=log2m, impl="numpy"))
    np.testing.assert_array_equal(ref, host)


def test_hash_join_numpy_matches_ref():
    rng = np.random.default_rng(11)
    b = rng.integers(0, 50, 200).astype(np.int64)
    p = rng.integers(0, 60, 300).astype(np.int64)  # some keys miss
    pi_r, bi_r = ops.hash_join_match(b, p, impl="ref")
    pi_n, bi_n = ops.hash_join_match(b, p, impl="numpy")
    np.testing.assert_array_equal(np.asarray(pi_r), pi_n)
    np.testing.assert_array_equal(np.asarray(bi_r), bi_n)


def test_masked_distance_numpy_matches_ref():
    rng = np.random.default_rng(13)
    q = rng.normal(size=(20, 6)).astype(np.float32)
    r = rng.normal(size=(30, 6)).astype(np.float32)
    qm = (rng.random((20, 6)) > 0.3).astype(np.float32)
    rm = (rng.random((30, 6)) > 0.3).astype(np.float32)
    dref = np.asarray(ops.masked_distance(q, qm, r, rm, impl="ref"))
    dnp = ops.masked_distance(q, qm, r, rm, impl="numpy")
    np.testing.assert_allclose(dref, dnp, rtol=1e-4, atol=1e-4)


def test_impl_resolvers_honor_env_knobs(monkeypatch):
    monkeypatch.setenv("QUIP_BLOOM_IMPL", "numpy")
    assert ops.resolve_bloom_impl() == "numpy"
    monkeypatch.setenv("QUIP_DIST_IMPL", "ref")
    assert ops.resolve_dist_impl() == "ref"
    with pytest.raises(ValueError):
        ops.resolve_join_impl("vectorwise")
    monkeypatch.delenv("QUIP_BLOOM_IMPL")
    assert ops.resolve_bloom_impl() in ("ref", "pallas")  # default_impl()
