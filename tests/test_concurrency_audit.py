"""Concurrency audit family: the static auditor's four passes
(``bigdl_tpu/analysis/concurrency.py``) rule by rule on purpose-built
fixtures (positive + suppressed + out-of-scope), the BDL017–BDL019 wiring
through ``tools/lint_framework.py``, the repo-clean gate, thread-entry-map
resolution on the real ``serving/batcher.py``, the committed lock-order
graph, the runtime lock sanitizer (``analysis/lock_tracer.py``) end to end
— including a chaos-``delay``-seeded hold-time breach and a deliberate
lock-order inversion with schema-valid ``warn`` telemetry — and regression
tests for the genuine findings this audit fixed."""

import importlib.util
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, str(path))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses resolve via sys.modules
    spec.loader.exec_module(mod)
    return mod


conc = _load("conc_audit", REPO / "bigdl_tpu" / "analysis" / "concurrency.py")
lint = _load("lint_framework_for_conc", REPO / "tools" / "lint_framework.py")
obs_report = _load("obs_report_for_conc", REPO / "tools" / "obs_report.py")

# the auditor and the lint bridge are pure stdlib — importable with no jax
from bigdl_tpu.analysis import lock_tracer  # noqa: E402  (jax ok in tests)


def run_audit(tmp_path, name, source):
    f = tmp_path / name
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(source)
    return conc.audit_paths([str(f)])


def run_lint(tmp_path, name, source):
    f = tmp_path / name
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(source)
    return lint.lint_paths([str(f)])


def codes(findings):
    return [f.code for f in findings]


_SPAWN_HELPER = (
    "import threading\n"
    "def spawn_worker(target, name=None):\n"
    "    t = threading.Thread(target=target, daemon=True)\n"
    "    t.start()\n"
    "    return t\n"
)


# ---------------------------------------------------------------------------
# BDL017: unguarded cross-thread state
# ---------------------------------------------------------------------------
class TestBDL017:
    def test_annotated_guard_unlocked_read_flagged(self, tmp_path):
        found = run_audit(tmp_path, "serving/queue.py", _SPAWN_HELPER + (
            "class Box:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._count = 0  # guarded-by: _lock\n"
            "        spawn_worker(self._loop)\n"
            "    def _loop(self):\n"
            "        with self._lock:\n"
            "            self._count += 1\n"
            "    def read(self):\n"
            "        return self._count\n"
        ))
        assert codes(found) == ["BDL017"]
        assert "annotated" in found[0].message
        assert "_lock" in found[0].message

    def test_inference_requires_all_writes_to_agree(self, tmp_path):
        # the unlocked write in poke() breaks the common-lock set, so no
        # guard is inferred (and nothing is flagged): inference is
        # deliberately conservative — mixed discipline needs an annotation
        found = run_audit(tmp_path, "serving/queue.py", _SPAWN_HELPER + (
            "class Box:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0\n"
            "        spawn_worker(self._loop)\n"
            "    def _loop(self):\n"
            "        with self._lock:\n"
            "            self._n += 1\n"
            "        self.poke()\n"
            "    def poke(self):\n"
            "        self._n = 0\n"
        ))
        assert codes(found) == []

    def test_inferred_guard_unlocked_read_flagged(self, tmp_path):
        found = run_audit(tmp_path, "serving/queue.py", _SPAWN_HELPER + (
            "class Box:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0\n"
            "        spawn_worker(self._loop)\n"
            "    def _loop(self):\n"
            "        with self._lock:\n"
            "            self._n += 1\n"
            "    def read(self):\n"
            "        return self._n\n"
        ))
        assert codes(found) == ["BDL017"]
        assert "inferred" in found[0].message

    def test_locked_access_clean(self, tmp_path):
        found = run_audit(tmp_path, "serving/queue.py", _SPAWN_HELPER + (
            "class Box:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0  # guarded-by: _lock\n"
            "        spawn_worker(self._loop)\n"
            "    def _loop(self):\n"
            "        with self._lock:\n"
            "            self._n += 1\n"
            "    def read(self):\n"
            "        with self._lock:\n"
            "            return self._n\n"
        ))
        assert found == []

    def test_single_thread_attr_clean(self, tmp_path):
        # no worker entry ever touches _n: no cross-thread race to flag
        found = run_audit(tmp_path, "serving/queue.py", (
            "import threading\n"
            "class Box:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0  # guarded-by: _lock\n"
            "    def bump(self):\n"
            "        with self._lock:\n"
            "            self._n += 1\n"
            "    def read(self):\n"
            "        return self._n\n"
        ))
        assert found == []

    def test_suppression_honored(self, tmp_path):
        found = run_audit(tmp_path, "serving/queue.py", _SPAWN_HELPER + (
            "class Box:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._count = 0  # guarded-by: _lock\n"
            "        spawn_worker(self._loop)\n"
            "    def _loop(self):\n"
            "        with self._lock:\n"
            "            self._count += 1\n"
            "    def read(self):\n"
            "        # monotone counter: a stale read is a valid snapshot\n"
            "        return self._count  # lint: disable=BDL017\n"
        ))
        assert found == []

    def test_out_of_scope_file_skipped(self, tmp_path):
        found = run_audit(tmp_path, "nn/linear.py", _SPAWN_HELPER + (
            "class Box:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._count = 0  # guarded-by: _lock\n"
            "        spawn_worker(self._loop)\n"
            "    def _loop(self):\n"
            "        with self._lock:\n"
            "            self._count += 1\n"
            "    def read(self):\n"
            "        return self._count\n"
        ))
        assert found == []

    def test_wired_through_lint_framework(self, tmp_path):
        found = run_lint(tmp_path, "obs/fleet.py", _SPAWN_HELPER + (
            "class Box:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._count = 0  # guarded-by: _lock\n"
            "        spawn_worker(self._loop)\n"
            "    def _loop(self):\n"
            "        with self._lock:\n"
            "            self._count += 1\n"
            "    def read(self):\n"
            "        return self._count\n"
        ))
        assert codes(found) == ["BDL017"]


# ---------------------------------------------------------------------------
# BDL018: wait/notify + blocking-under-hot-lock discipline
# ---------------------------------------------------------------------------
class TestBDL018:
    def test_wait_outside_while_flagged(self, tmp_path):
        found = run_audit(tmp_path, "dataset/pipeline.py", (
            "import threading\n"
            "class Ring:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._cond = threading.Condition(self._lock)\n"
            "        self._items = []\n"
            "    def get(self):\n"
            "        with self._cond:\n"
            "            if not self._items:\n"
            "                self._cond.wait()\n"
            "            return self._items.pop()\n"
        ))
        assert codes(found) == ["BDL018"]
        assert "while" in found[0].message

    def test_wait_in_while_under_lock_clean(self, tmp_path):
        found = run_audit(tmp_path, "dataset/pipeline.py", (
            "import threading\n"
            "class Ring:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._cond = threading.Condition(self._lock)\n"
            "        self._items = []\n"
            "    def get(self):\n"
            "        with self._cond:\n"
            "            while not self._items:\n"
            "                self._cond.wait()\n"
            "            return self._items.pop()\n"
            "    def put(self, x):\n"
            "        with self._cond:\n"
            "            self._items.append(x)\n"
            "            self._cond.notify()\n"
        ))
        assert found == []

    def test_notify_without_lock_flagged(self, tmp_path):
        found = run_audit(tmp_path, "dataset/pipeline.py", (
            "import threading\n"
            "class Ring:\n"
            "    def __init__(self):\n"
            "        self._cond = threading.Condition()\n"
            "    def wake(self):\n"
            "        self._cond.notify_all()\n"
        ))
        assert codes(found) == ["BDL018"]
        assert "notify" in found[0].message

    def test_event_wait_not_flagged(self, tmp_path):
        # MonitorBase idiom: self._stop is an Event, not a Condition — its
        # timed wait() is the sanctioned poll-loop sleep
        found = run_audit(tmp_path, "obs/watchdog.py", (
            "import threading\n"
            "class Monitor:\n"
            "    def __init__(self):\n"
            "        self._stop = threading.Event()\n"
            "    def _poll(self):\n"
            "        while not self._stop.wait(0.5):\n"
            "            pass\n"
        ))
        assert found == []

    def test_sleep_under_hot_lock_flagged(self, tmp_path):
        found = run_audit(tmp_path, "serving/batcher.py", (
            "import threading\n"
            "import time\n"
            "class Batcher:\n"
            "    def __init__(self):\n"
            "        self._swap_lock = threading.Lock()  # hot-lock: dispatch\n"
            "    def flush(self):\n"
            "        with self._swap_lock:\n"
            "            time.sleep(0.5)\n"
        ))
        assert codes(found) == ["BDL018"]
        assert "_swap_lock" in found[0].message

    def test_sleep_under_plain_lock_clean(self, tmp_path):
        found = run_audit(tmp_path, "serving/batcher.py", (
            "import threading\n"
            "import time\n"
            "class Batcher:\n"
            "    def __init__(self):\n"
            "        self._lk = threading.Lock()\n"
            "    def flush(self):\n"
            "        with self._lk:\n"
            "            time.sleep(0.5)\n"
        ))
        assert found == []

    def test_blocking_queue_get_under_hot_lock_flagged(self, tmp_path):
        found = run_audit(tmp_path, "serving/server.py", (
            "import queue\n"
            "import threading\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._lk = threading.Lock()  # hot-lock: mgmt\n"
            "        self._q = queue.Queue(maxsize=4)\n"
            "    def drain(self):\n"
            "        with self._lk:\n"
            "            return self._q.get()\n"
        ))
        assert codes(found) == ["BDL018"]

    def test_timed_queue_get_and_dict_get_clean(self, tmp_path):
        found = run_audit(tmp_path, "serving/server.py", (
            "import queue\n"
            "import threading\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._lk = threading.Lock()  # hot-lock: mgmt\n"
            "        self._q = queue.Queue(maxsize=4)\n"
            "        self._d = {}\n"
            "    def drain(self):\n"
            "        with self._lk:\n"
            "            x = self._q.get(timeout=0.1)\n"
            "            return x, self._d.get('k')\n"
        ))
        assert found == []

    def test_future_result_under_hot_lock_flagged(self, tmp_path):
        found = run_audit(tmp_path, "serving/server.py", (
            "import threading\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._lk = threading.Lock()  # hot-lock: mgmt\n"
            "    def wait_done(self, fut):\n"
            "        with self._lk:\n"
            "            return fut.result()\n"
        ))
        assert codes(found) == ["BDL018"]

    def test_own_condition_wait_not_blocking_under_own_lock(self, tmp_path):
        # wait() releases its own (hot) lock while blocked — must not be
        # treated as blocking-under-hot-lock
        found = run_audit(tmp_path, "serving/queue.py", (
            "import threading\n"
            "class Q:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()  # hot-lock: queue\n"
            "        self._cond = threading.Condition(self._lock)\n"
            "        self._items = []\n"
            "    def get(self):\n"
            "        with self._cond:\n"
            "            while not self._items:\n"
            "                self._cond.wait()\n"
            "            return self._items.pop()\n"
        ))
        assert found == []

    def test_suppression_honored(self, tmp_path):
        found = run_audit(tmp_path, "serving/batcher.py", (
            "import threading\n"
            "import time\n"
            "class Batcher:\n"
            "    def __init__(self):\n"
            "        self._swap_lock = threading.Lock()  # hot-lock: dispatch\n"
            "    def flush(self):\n"
            "        with self._swap_lock:\n"
            "            # bounded 1ms settle, measured, see docs\n"
            "            time.sleep(0.001)  # lint: disable=BDL018\n"
        ))
        assert found == []


# ---------------------------------------------------------------------------
# BDL019: lock-order cycles
# ---------------------------------------------------------------------------
class TestBDL019:
    def test_opposite_order_cycle_flagged(self, tmp_path):
        found = run_audit(tmp_path, "serving/server.py", (
            "import threading\n"
            "class P:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "    def ab(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                pass\n"
            "    def ba(self):\n"
            "        with self._b:\n"
            "            with self._a:\n"
            "                pass\n"
        ))
        assert codes(found) == ["BDL019"]
        assert "P._a" in found[0].message and "P._b" in found[0].message

    def test_consistent_order_clean(self, tmp_path):
        found = run_audit(tmp_path, "serving/server.py", (
            "import threading\n"
            "class P:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "    def ab(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                pass\n"
            "    def also_ab(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                pass\n"
        ))
        assert found == []

    def test_interprocedural_cycle_flagged(self, tmp_path):
        # ab() holds _a and CALLS take_b() (which acquires _b); ba() nests
        # directly in the opposite order — only the one-call-deep edge
        # closes the cycle
        found = run_audit(tmp_path, "serving/server.py", (
            "import threading\n"
            "class P:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "    def take_b(self):\n"
            "        with self._b:\n"
            "            pass\n"
            "    def ab(self):\n"
            "        with self._a:\n"
            "            self.take_b()\n"
            "    def ba(self):\n"
            "        with self._b:\n"
            "            with self._a:\n"
            "                pass\n"
        ))
        assert codes(found) == ["BDL019"]

    def test_cross_class_nesting_via_typed_attr(self, tmp_path):
        # holding Outer._lk while calling into a typed attribute whose
        # method takes Inner._lk registers the cross-class edge
        src = (
            "import threading\n"
            "class Inner:\n"
            "    def __init__(self):\n"
            "        self._lk = threading.Lock()\n"
            "    def poke(self):\n"
            "        with self._lk:\n"
            "            pass\n"
            "class Outer:\n"
            "    def __init__(self):\n"
            "        self._lk = threading.Lock()\n"
            "        self._inner = Inner()\n"
            "    def run(self):\n"
            "        with self._lk:\n"
            "            self._inner.poke()\n"
        )
        f = tmp_path / "serving" / "server.py"
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(src)
        prog, errs = conc.build_program([str(f)])
        assert not errs
        edges = conc.lock_order_graph(prog)
        names = {(f"{a[0]}.{a[1]}", f"{b[0]}.{b[1]}") for a, b in edges}
        assert ("Outer._lk", "Inner._lk") in names

    def test_suppression_honored(self, tmp_path):
        found = run_audit(tmp_path, "serving/server.py", (
            "import threading\n"
            "class P:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "    def ab(self):\n"
            "        with self._a:\n"
            "            with self._b:  # lint: disable=BDL019\n"
            "                pass\n"
            "    def ba(self):\n"
            "        with self._b:\n"
            "            with self._a:\n"
            "                pass\n"
        ))
        assert found == []


# ---------------------------------------------------------------------------
# repo gates: audit-clean, selftest, entry map, committed lock-order graph
# ---------------------------------------------------------------------------
class TestRepoGates:
    def test_repo_audit_clean(self):
        assert conc.audit_paths([str(REPO / "bigdl_tpu")]) == []

    def test_auditor_selftest_passes(self):
        r = subprocess.run(
            [sys.executable, str(REPO / "bigdl_tpu" / "analysis" /
                                 "concurrency.py"), "--selftest"],
            capture_output=True, text=True, cwd=str(REPO),
        )
        assert r.returncode == 0, r.stdout + r.stderr

    def test_lint_gate_includes_concurrency_rules(self):
        r = subprocess.run(
            [sys.executable, str(REPO / "tools" / "lint_framework.py"),
             "bigdl_tpu", "tools"],
            capture_output=True, text=True, cwd=str(REPO),
        )
        assert r.returncode == 0, r.stdout + r.stderr

    def _repo_program(self):
        files = conc.scope_filter(
            conc.iter_py_files([str(REPO / "bigdl_tpu")])
        )
        prog, errs = conc.build_program(files)
        assert not errs
        return prog

    def test_entry_map_resolves_real_batcher(self):
        em = conc.entry_map(self._repo_program())
        # spawn_worker(self._run) puts the whole flush chain on the worker
        assert "worker:ContinuousBatcher._run" in em["ContinuousBatcher._run"]
        assert "worker:ContinuousBatcher._run" in em["ContinuousBatcher._flush"]
        # submit stays caller-side
        assert "main" in em["ContinuousBatcher.submit"]
        # MonitorBase subclasses put check() on the monitor thread
        assert any(t.startswith("monitor:") for t in em["StallWatchdog.check"])
        assert any(t.startswith("monitor:") for t in em["FleetMonitor.check"])
        # nested pipeline worker closures are their own thread entries
        nested = [q for q in em if ".<" in q and any(
            t.startswith("worker:") for t in em[q]
        )]
        assert nested, "no nested worker closures resolved"

    def test_committed_lock_order_graph(self):
        prog = self._repo_program()
        edges = conc.lock_order_graph(prog)
        names = {(f"{a[0]}.{a[1]}", f"{b[0]}.{b[1]}") for a, b in edges}
        # the serving tier's two sanctioned nestings
        assert ("ContinuousBatcher._swap_lock",
                "ContinuousBatcher._acct_lock") in names
        assert ("ModelServer._mgmt_lock", "ModelServer._lock") in names
        assert conc.find_cycles(edges) == []

    def test_static_order_edges_helper(self):
        edges = conc.static_order_edges([str(REPO / "bigdl_tpu")])
        assert ("ContinuousBatcher._swap_lock",
                "ContinuousBatcher._acct_lock") in edges


# ---------------------------------------------------------------------------
# runtime lock sanitizer
# ---------------------------------------------------------------------------
class _Pair:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()


class TestLockTracer:
    def test_disabled_is_zero_overhead_noop(self, monkeypatch):
        monkeypatch.delenv("BIGDL_LOCK_DEBUG", raising=False)
        o = _Pair()
        raw = o._a
        assert lock_tracer.instrument_locks(o) == []
        assert o._a is raw  # untouched: raw threading primitive

    def test_runtime_inversion_and_chaos_delay_hold_breach(self, monkeypatch):
        """End to end: two threads take the pair in opposite orders (the
        seeded inversion), and a chaos ``delay`` fault inside the first
        critical section stretches the hold past the limit — both must
        surface as schema-valid ``warn`` telemetry records."""
        from bigdl_tpu.obs import Telemetry
        from bigdl_tpu.obs.trace import fault_point
        from bigdl_tpu.resilience import FaultPlan

        monkeypatch.setenv("BIGDL_LOCK_DEBUG", "1")
        tel = Telemetry(exporters=[])
        tr = lock_tracer.LockTracer(telemetry=tel, hold_warn_s=0.05)
        o = _Pair()
        assert lock_tracer.instrument_locks(o, tracer=tr) == [
            "_Pair._a", "_Pair._b",
        ]

        def ab():
            with o._a:
                fault_point("lock_audit_hold")  # chaos delay stretches hold
                with o._b:
                    pass

        def ba():
            with o._b:
                with o._a:
                    pass

        with FaultPlan().arm("lock_audit_hold", kind="delay", delay_s=0.12):
            t = threading.Thread(target=ab)
            t.start()
            t.join()
        t = threading.Thread(target=ba)
        t.start()
        t.join()

        assert [i["kind"] for i in tr.inversions] == ["runtime"]
        assert tr.hold_breaches and tr.hold_breaches[0]["lock"] == "_Pair._a"
        assert tr.hold_breaches[0]["held_s"] >= 0.12
        warns = [r for r in tel.ring.records if r["type"] == "warn"]
        reasons = {w["reason"] for w in warns}
        assert "lock_order_inversion" in reasons
        assert "lock_hold_exceeded" in reasons
        for w in warns:
            obs_report.validate_record(w)  # schema-valid telemetry

    def test_static_graph_contradiction_flagged(self, monkeypatch):
        monkeypatch.setenv("BIGDL_LOCK_DEBUG", "1")
        tr = lock_tracer.LockTracer(
            static_edges={("_Pair._a", "_Pair._b")}
        )
        o = _Pair()
        lock_tracer.instrument_locks(o, tracer=tr)
        with o._b:  # static graph says _a before _b: this order contradicts
            with o._a:
                pass
        assert [i["kind"] for i in tr.inversions] == ["static"]

    def test_consistent_order_and_short_holds_stay_quiet(self, monkeypatch):
        monkeypatch.setenv("BIGDL_LOCK_DEBUG", "1")
        tr = lock_tracer.LockTracer(
            static_edges={("_Pair._a", "_Pair._b")}, hold_warn_s=5.0
        )
        o = _Pair()
        lock_tracer.instrument_locks(o, tracer=tr)
        for _ in range(3):
            with o._a:
                with o._b:
                    pass
        assert tr.inversions == []
        assert tr.hold_breaches == []
        assert ("_Pair._a", "_Pair._b") in tr.edges

    def test_rlock_reentry_records_once(self, monkeypatch):
        monkeypatch.setenv("BIGDL_LOCK_DEBUG", "1")

        class R:
            def __init__(self):
                self._r = threading.RLock()

        tr = lock_tracer.LockTracer(hold_warn_s=5.0)
        o = R()
        lock_tracer.instrument_locks(o, tracer=tr)
        with o._r:
            with o._r:  # reentrant: depth-counted, no self-edge
                pass
        assert tr.inversions == []
        assert all(a != b for (a, b) in tr.edges)

    def test_real_batcher_agrees_with_static_graph(self, monkeypatch):
        """Static/runtime agreement on the clean repo: a real
        ``ContinuousBatcher`` flow, instrumented against the auditor's
        committed lock-order graph, must observe no inversion."""
        from bigdl_tpu import nn
        from bigdl_tpu.optim.predictor import Predictor
        from bigdl_tpu.serving import ContinuousBatcher, ServeRequest
        from bigdl_tpu.utils.random import RandomGenerator

        monkeypatch.setenv("BIGDL_LOCK_DEBUG", "1")
        RandomGenerator.set_seed(7)
        m = nn.Sequential(nn.Linear(6, 8), nn.ReLU(), nn.Linear(8, 3))
        m.init(sample_input=np.zeros((1, 6), np.float32))
        pred = Predictor(m, batch_size=4)
        b = ContinuousBatcher(pred, name="m", max_delay_ms=5.0)
        static = lock_tracer.load_static_edges([str(REPO / "bigdl_tpu")])
        tr = lock_tracer.LockTracer(static_edges=static, hold_warn_s=30.0)
        traced = lock_tracer.instrument_locks(b, tracer=tr)
        assert "ContinuousBatcher._swap_lock" in traced
        assert "ContinuousBatcher._acct_lock" in traced
        b.start()
        try:
            futs = [
                b.submit(ServeRequest(np.zeros(6, np.float32)))
                for _ in range(6)
            ]
            for f in futs:
                f.result(timeout=30)
        finally:
            b.stop()
        assert tr.inversions == []
        # the committed static nesting actually ran
        assert ("ContinuousBatcher._swap_lock",
                "ContinuousBatcher._acct_lock") in tr.edges


# ---------------------------------------------------------------------------
# regression tests for the genuine findings this audit fixed
# ---------------------------------------------------------------------------
class TestSatelliteFixes:
    def test_watchdog_callbacks_locked_and_fired_outside_lock(self):
        """PR-16 fix: StallWatchdog._callbacks crosses threads (driver
        registers, monitor fires) — mutations now hold _lock, and the stall
        path snapshots under the lock but fires hooks OUTSIDE it (a hook
        must be able to call back into the watchdog)."""
        from bigdl_tpu.obs.watchdog import StallWatchdog

        now = [0.0]
        wd = StallWatchdog(k=2.0, min_timeout_s=1.0, clock=lambda: now[0])
        lock_free = []

        def probe():
            # acquire from ANOTHER thread: an RLock held by the firing
            # thread would make a same-thread probe succeed vacuously
            got = wd._lock.acquire(timeout=1.0)
            if got:
                wd._lock.release()
            lock_free.append(got)

        def hook(info):
            t = threading.Thread(target=probe)
            t.start()
            t.join()

        wd.add_callback(hook)
        wd.remove_callback(hook)
        wd.add_callback(hook)
        wd.notify_step(0.5)
        now[0] = 10.0  # way past k * estimate
        info = wd.check()
        assert info is not None
        assert lock_free == [True]

    def test_fleet_callbacks_locked_and_fired_outside_lock(self, tmp_path):
        """PR-16 fix: FleetMonitor gained a _lock guarding _callbacks; the
        event path snapshots under it and fires hooks outside it."""
        from bigdl_tpu.obs.fleet import FleetMonitor, write_heartbeat

        now = 1000.0
        write_heartbeat(str(tmp_path), identity={"process_index": 0},
                        step=100, clock=lambda: now)
        write_heartbeat(str(tmp_path), identity={"process_index": 1},
                        step=100, clock=lambda: now - 500.0)  # stale
        fm = FleetMonitor(str(tmp_path), stale_after_s=60.0,
                          wall_clock=lambda: now)
        lock_free = []
        fm.add_callback(
            lambda ev: lock_free.append(fm._lock.acquire(blocking=False))
        )
        events = fm.check()
        for got in lock_free:
            if got:
                fm._lock.release()
        assert [e["reason"] for e in events] == ["host_lost"]
        assert lock_free == [True]

    def test_swap_validates_geometry_under_lock(self):
        """PR-16 fix: swap() used to read self.predictor's geometry BEFORE
        taking _swap_lock (TOCTOU against a concurrent swap); the check now
        runs under the lock. Behavior: mismatched geometry still rejected,
        matching geometry still swaps."""
        from bigdl_tpu import nn
        from bigdl_tpu.optim.predictor import Predictor
        from bigdl_tpu.serving import ContinuousBatcher
        from bigdl_tpu.utils.random import RandomGenerator

        RandomGenerator.set_seed(11)
        m = nn.Sequential(nn.Linear(6, 8), nn.ReLU(), nn.Linear(8, 3))
        m.init(sample_input=np.zeros((1, 6), np.float32))
        b = ContinuousBatcher(Predictor(m, batch_size=4), name="m")
        with pytest.raises(ValueError, match="identical batch_size"):
            b.swap(Predictor(m, batch_size=8), version=2)
        assert b.version == 1
        b.swap(Predictor(m, batch_size=4), version=2)
        assert b.version == 2

    def test_assembly_failure_resolves_futures_with_version(self):
        """PR-16 fix: the assembly-failure path read (predictor, _version)
        without _swap_lock — a torn read could blame the error on the wrong
        version's accounting. Behavior: ragged features still fail the whole
        batch with the assembly error, futures resolved, worker alive."""
        from bigdl_tpu import nn
        from bigdl_tpu.optim.predictor import Predictor
        from bigdl_tpu.serving import ContinuousBatcher, ServeRequest
        from bigdl_tpu.utils.random import RandomGenerator

        RandomGenerator.set_seed(13)
        m = nn.Sequential(nn.Linear(6, 8), nn.ReLU(), nn.Linear(8, 3))
        m.init(sample_input=np.zeros((1, 6), np.float32))
        b = ContinuousBatcher(Predictor(m, batch_size=4), name="m",
                              max_delay_ms=5.0)
        b.start()
        try:
            f1 = b.submit(ServeRequest(np.zeros(6, np.float32)))
            f2 = b.submit(ServeRequest(np.zeros(7, np.float32)))  # ragged
            with pytest.raises(Exception):
                f1.result(timeout=30)
            with pytest.raises(Exception):
                f2.result(timeout=30)
            # the batching thread survived the assembly failure
            f3 = b.submit(ServeRequest(np.zeros(6, np.float32)))
            assert f3.result(timeout=30) is not None
        finally:
            b.stop()
