"""dmlc-check static-analysis suite tests.

Three layers:
  * fixture snippets per pass — each seeded-bad snippet is caught and
    its clean counterpart passes (the framework's regression suite);
  * whole-repo invariants — the real tree runs clean, and the knob
    registry is cross-checked against an independent grep of every
    ``DMLC_*`` env read (so the registry cannot silently miss a knob);
  * the runtime lock-order watchdog (``DMLC_LOCKCHECK=1``) — a
    provoked inversion across two threads and a held-while-blocked
    acquire are both recorded, clean runs record nothing.
"""

import os
import re
import threading
import time

import pytest

from dmlc_tpu import concurrency, config_registry
from dmlc_tpu.analysis import ALL_PASSES, run_passes
from dmlc_tpu.analysis.concurrency_pass import ConcurrencyPass
from dmlc_tpu.analysis.contract_pass import ContractPass
from dmlc_tpu.analysis.core import RepoIndex, default_paths, repo_root
from dmlc_tpu.analysis.knob_pass import KnobPass
from dmlc_tpu.analysis.metrics_pass import MetricsPass
from dmlc_tpu.analysis.style_pass import StylePass

REPO = repo_root()


# ---------------------------------------------------------------------------
# fixture harness: a throwaway mini-repo so path-scoped rules apply
# ---------------------------------------------------------------------------

def _index(tmp_path, files):
    paths = []
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
        paths.append(str(p))
    return RepoIndex(paths, str(tmp_path))


def _checks(findings, check):
    return [f for f in findings if f.check == check]


# ---- concurrency pass --------------------------------------------------

BAD_BLOCKING = '''\
import threading
import time

_lock = threading.Lock()


def slow():
    with _lock:
        time.sleep(1.0)
'''

CLEAN_BLOCKING = '''\
import threading
import time

_lock = threading.Lock()


def fast():
    with _lock:
        x = 1
    time.sleep(1.0)
    return x
'''


def test_blocking_under_lock_caught(tmp_path):
    idx = _index(tmp_path, {"dmlc_tpu/mod.py": BAD_BLOCKING})
    found = ConcurrencyPass().run(idx)
    assert _checks(found, "blocking-under-lock"), found


def test_blocking_under_lock_clean(tmp_path):
    idx = _index(tmp_path, {"dmlc_tpu/mod.py": CLEAN_BLOCKING})
    assert not _checks(ConcurrencyPass().run(idx), "blocking-under-lock")


BAD_INVERSION = '''\
import threading


class M:
    def __init__(self):
        self._a_lock = threading.Lock()
        self._b_lock = threading.Lock()

    def one(self):
        with self._a_lock:
            with self._b_lock:
                return 1

    def two(self):
        with self._b_lock:
            with self._a_lock:
                return 2
'''

CLEAN_NESTING = BAD_INVERSION.replace(
    "        with self._b_lock:\n            with self._a_lock:",
    "        with self._a_lock:\n            with self._b_lock:")


def test_lock_inversion_caught(tmp_path):
    idx = _index(tmp_path, {"dmlc_tpu/mod.py": BAD_INVERSION})
    found = _checks(ConcurrencyPass().run(idx), "lock-cycle")
    assert found and "M._a_lock" in str(found[0]), found


def test_lock_nesting_consistent_clean(tmp_path):
    idx = _index(tmp_path, {"dmlc_tpu/mod.py": CLEAN_NESTING})
    assert not _checks(ConcurrencyPass().run(idx), "lock-cycle")


def test_lock_cycle_via_call_propagation(tmp_path):
    src = '''\
import threading


class A:
    def __init__(self, b):
        self._a_lock = threading.Lock()
        self.b = b

    def go(self):
        with self._a_lock:
            self.b.poke()


class B:
    def __init__(self, a):
        self._b_lock = threading.Lock()
        self.a = a

    def poke(self):
        with self._b_lock:
            return 1

    def back(self):
        with self._b_lock:
            self.a.go()
'''
    idx = _index(tmp_path, {"dmlc_tpu/mod.py": src})
    assert _checks(ConcurrencyPass().run(idx), "lock-cycle")


def test_non_daemon_thread_caught(tmp_path):
    bad = ("import threading\n\n\n"
           "def spawn(fn):\n"
           "    t = threading.Thread(target=fn)\n"
           "    t.start()\n")
    idx = _index(tmp_path, {"dmlc_tpu/mod.py": bad})
    assert _checks(ConcurrencyPass().run(idx), "non-daemon-thread")
    ok = bad.replace("target=fn)", "target=fn, daemon=True)")
    idx = _index(tmp_path, {"dmlc_tpu/mod.py": ok})
    assert not _checks(ConcurrencyPass().run(idx), "non-daemon-thread")
    joined = bad + "    t.join()\n"
    idx = _index(tmp_path, {"dmlc_tpu/mod.py": joined})
    assert not _checks(ConcurrencyPass().run(idx), "non-daemon-thread")


# ---- knob pass ---------------------------------------------------------

def test_unregistered_knob_caught(tmp_path):
    src = ("from dmlc_tpu.base import get_env\n\n"
           "v = get_env(\"DMLC_NO_SUCH_KNOB_EVER\", 1)\n")
    idx = _index(tmp_path, {"dmlc_tpu/mod.py": src})
    assert _checks(KnobPass().run(idx), "unregistered-knob")
    ok = src.replace("DMLC_NO_SUCH_KNOB_EVER", "DMLC_FEED_DEPTH")
    idx = _index(tmp_path, {"dmlc_tpu/mod.py": ok})
    assert not KnobPass().run(idx)


def test_raw_env_read_caught_in_package_only(tmp_path):
    src = "import os\n\nv = os.environ.get(\"DMLC_FEED_DEPTH\")\n"
    idx = _index(tmp_path, {"dmlc_tpu/mod.py": src})
    assert _checks(KnobPass().run(idx), "raw-env-read")
    # the same read in scripts/ is allowed (package-only invariant)
    idx = _index(tmp_path, {"scripts/mod.py": src})
    assert not _checks(KnobPass().run(idx), "raw-env-read")


def test_unknown_knob_token_caught(tmp_path):
    src = 'DOC = "set DMLC_TOTALLY_MADE_UP to tune nothing"\n'
    idx = _index(tmp_path, {"dmlc_tpu/mod.py": src})
    assert _checks(KnobPass().run(idx), "unknown-knob-token")
    # family-prefix mentions of real knobs are fine
    ok = 'DOC = "the DMLC_COLL_ knobs must be gang-uniform"\n'
    idx = _index(tmp_path, {"dmlc_tpu/mod.py": ok})
    assert not KnobPass().run(idx)


def test_pass_envs_missing_caught(tmp_path):
    launch = ('PASS_ENVS = [\n    "DMLC_INTERFACE",\n]\n')
    idx = _index(tmp_path, {"dmlc_tpu/tracker/launch.py": launch})
    missing = _checks(KnobPass().run(idx), "pass-envs-missing")
    # every other pass_to_workers knob is reported missing
    assert len(missing) == len(config_registry.pass_env_names()) - 1


def test_pass_envs_unknown_caught(tmp_path):
    launch = ('PASS_ENVS = [\n    "DMLC_BOGUS_FORWARD",\n]\n')
    idx = _index(tmp_path, {"dmlc_tpu/tracker/launch.py": launch})
    assert _checks(KnobPass().run(idx), "pass-envs-unknown")


# ---- contract pass -----------------------------------------------------

SWALLOW = '''\
def pull(sock):
    try:
        return sock.recv_thing()
    except Exception:
        return None
'''


def test_swallowed_exception_caught_in_protected_path(tmp_path):
    idx = _index(tmp_path, {"dmlc_tpu/tracker/client.py": SWALLOW})
    assert _checks(ContractPass().run(idx), "swallowed-exception")
    # same handler outside the protected paths is fine
    idx = _index(tmp_path, {"dmlc_tpu/telemetry/foo.py": SWALLOW})
    assert not _checks(ContractPass().run(idx), "swallowed-exception")


def test_swallow_ok_when_protected_type_handled_first(tmp_path):
    src = '''\
from ..base import DMLCError
from .client import WorldResized


def pull(sock):
    try:
        return sock.recv_thing()
    except WorldResized:
        raise
    except Exception:
        return None
'''
    idx = _index(tmp_path, {"dmlc_tpu/tracker/client.py": src})
    assert not _checks(ContractPass().run(idx), "swallowed-exception")


def test_swallow_ok_when_transported(tmp_path):
    src = '''\
def pull(sock, fut):
    try:
        return sock.recv_thing()
    except BaseException as e:
        fut.set_exception(e)
'''
    idx = _index(tmp_path, {"dmlc_tpu/tracker/client.py": src})
    assert not _checks(ContractPass().run(idx), "swallowed-exception")


def test_socket_no_timeout_caught(tmp_path):
    bad = ("import socket\n\n\n"
           "def dial():\n"
           "    s = socket.socket()\n"
           "    return s\n")
    idx = _index(tmp_path, {"dmlc_tpu/mod.py": bad})
    assert _checks(ContractPass().run(idx), "socket-no-timeout")
    ok = bad.replace("    return s\n",
                     "    s.settimeout(5.0)\n    return s\n")
    idx = _index(tmp_path, {"dmlc_tpu/mod.py": ok})
    assert not _checks(ContractPass().run(idx), "socket-no-timeout")


def test_typod_fault_site_caught(tmp_path):
    bad = 'SPEC = "tracker.dail=error::2"\n'  # typo'd tracker.dial
    idx = _index(tmp_path, {"dmlc_tpu/mod.py": bad})
    assert _checks(ContractPass().run(idx), "unknown-fault-site")


def test_fault_site_resolves_against_instrumented_calls(tmp_path):
    src = ('from dmlc_tpu.resilience import fault_point\n\n'
           'SPEC = "my.site@rank:1=kill:137"\n\n\n'
           'def go(rank):\n'
           '    fault_point("my.site", rank=rank)\n')
    idx = _index(tmp_path, {"dmlc_tpu/mod.py": src})
    assert not _checks(ContractPass().run(idx), "unknown-fault-site")


def test_fault_site_in_embedded_worker_source_counts(tmp_path):
    src = ("WORKER = '''\n"
           "from dmlc_tpu.resilience import fault_point\n"
           'fault_point("embedded.site", rank=0)\n'
           "'''\n"
           'SPEC = "embedded.site=delay:0.1"\n')
    idx = _index(tmp_path, {"scripts/smoke.py": src})
    assert not _checks(ContractPass().run(idx), "unknown-fault-site")


# ---- style / metrics passes (absorbed lint.py) -------------------------

def test_style_pass_catches_classics(tmp_path):
    src = ("import os\n\n\n"
           "def f(x=[]):\n"
           "    try:\n"
           "        return x\n"
           "    except:\n"
           "        pass\n")
    idx = _index(tmp_path, {"dmlc_tpu/mod.py": src})
    found = StylePass().run(idx)
    for check in ("unused-import", "mutable-default", "bare-except"):
        assert _checks(found, check), (check, found)


def test_metrics_pass_catches_unregistered_family(tmp_path):
    src = ('from dmlc_tpu import telemetry\n\n'
           'telemetry.inc("bogus_stage", "bogus_name")\n')
    idx = _index(tmp_path, {"dmlc_tpu/mod.py": src})
    assert _checks(MetricsPass().run(idx), "metric-name")


def test_metrics_pass_derives_a_spans_counter_pair(tmp_path):
    src = ('from dmlc_tpu import telemetry\n\n'
           'with telemetry.span("bogus.a.b", stage="bogus"):\n'
           '    pass\n'
           'with telemetry.span("serving.decode.fetch", stage="serving"):\n'
           '    pass\n')
    idx = _index(tmp_path, {"dmlc_tpu/mod.py": src})
    found = _checks(MetricsPass().run(idx), "metric-name")
    # the registered serving pair passes; the bogus span's pair does not
    # (built, not spelled: a literal would be a finding of this tree)
    assert sorted(f.message.split("'")[1] for f in found) == [
        "dmlc" + "_bogus_a_b" + kind for kind in ("_count", "_secs")]


def test_suppression_comment_and_counting(tmp_path):
    src = ("import threading\n"
           "import time\n\n"
           "_lock = threading.Lock()\n\n\n"
           "def slow():\n"
           "    with _lock:\n"
           "        # dmlc-check: disable=blocking-under-lock -- test\n"
           "        time.sleep(1.0)\n")
    idx = _index(tmp_path, {"dmlc_tpu/mod.py": src})
    findings, suppressed = run_passes(idx, [ConcurrencyPass()])
    assert not findings
    assert [s.check for s in suppressed] == ["blocking-under-lock"]


# ---------------------------------------------------------------------------
# whole-repo invariants
# ---------------------------------------------------------------------------

def _repo_index():
    roots = ["dmlc_tpu", "tests", "scripts", "examples", "bench.py",
             "__graft_entry__.py", "bin"]
    return RepoIndex(default_paths(roots, REPO), REPO)


def test_repo_runs_clean():
    """The shipped tree passes every dmlc-check pass (suppressions
    allowed — they are inline-visible and counted)."""
    idx = _repo_index()
    findings, _suppressed = run_passes(idx, [cls() for cls in ALL_PASSES])
    assert not findings, "\n".join(str(f) for f in findings[:40])


_READ_RE = re.compile(
    r"(?:os\.environ(?:\.get)?\s*[\[\(]|os\.getenv\(|get_env\()"
    r"\s*[\"'](DMLC_[A-Z0-9_]+)[\"']")


def test_registry_covers_every_env_read_grep():
    """Independent cross-check: a raw regex grep over dmlc_tpu/ (no AST,
    no shared code with the knob pass) finds no env read the registry
    does not know."""
    known = set(config_registry.names())
    unknown = {}
    for dirpath, dirnames, filenames in os.walk(
            os.path.join(REPO, "dmlc_tpu")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path, encoding="utf-8") as f:
                src = f.read()
            for key in _READ_RE.findall(src):
                if key not in known:
                    unknown.setdefault(key, path)
    assert not unknown, unknown


def test_pass_envs_matches_registry():
    from dmlc_tpu.tracker.launch import PASS_ENVS

    missing = [k for k in config_registry.pass_env_names()
               if k not in PASS_ENVS]
    assert not missing, missing
    bogus = [k for k in PASS_ENVS if k.startswith("DMLC_")
             and config_registry.get(k) is None]
    assert not bogus, bogus


def test_readme_knob_table_current():
    from dmlc_tpu.analysis.knob_pass import readme_with_table

    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
        src = f.read()
    want = readme_with_table(src, config_registry.render_markdown_table())
    assert want == src, ("README knob table drifted — run "
                         "scripts/dmlc_check.py --write-knob-table")


def test_registry_table_lists_every_knob():
    table = config_registry.render_markdown_table()
    for k in config_registry.names():
        assert f"`{k}`" in table, k


# ---------------------------------------------------------------------------
# runtime lock-order watchdog
# ---------------------------------------------------------------------------

@pytest.fixture
def lockcheck(monkeypatch):
    monkeypatch.setenv("DMLC_LOCKCHECK", "1")
    concurrency.lockcheck_reset()
    yield
    concurrency.lockcheck_reset()


def test_make_lock_plain_when_disabled(monkeypatch):
    monkeypatch.delenv("DMLC_LOCKCHECK", raising=False)
    lk = concurrency.make_lock("x")
    assert not isinstance(lk, concurrency.CheckedLock)
    with lk:
        pass


def test_watchdog_flags_inversion_across_threads(lockcheck):
    a = concurrency.make_lock("test.A")
    b = concurrency.make_lock("test.B")

    def ab():
        with a:
            with b:
                pass

    def ba():
        with b:
            with a:
                pass

    # the two threads never overlap in time — a stress test would pass;
    # the order graph still convicts the pair
    t1 = threading.Thread(target=ab, daemon=True)
    t1.start()
    t1.join()
    t2 = threading.Thread(target=ba, daemon=True)
    t2.start()
    t2.join()
    kinds = [v["kind"] for v in concurrency.lockcheck_report()]
    assert "order-inversion" in kinds
    with pytest.raises(Exception, match="order-inversion"):
        concurrency.lockcheck_assert_clean()


def test_watchdog_clean_on_consistent_order(lockcheck):
    a = concurrency.make_lock("test.C")
    b = concurrency.make_lock("test.D")

    def ab():
        with a:
            with b:
                pass

    for _ in range(2):
        t = threading.Thread(target=ab, daemon=True)
        t.start()
        t.join()
    with a:
        with b:
            pass
    assert concurrency.lockcheck_report() == []
    concurrency.lockcheck_assert_clean()


def test_watchdog_flags_held_while_blocked(lockcheck, monkeypatch):
    monkeypatch.setenv("DMLC_LOCKCHECK_BLOCK_S", "0.1")
    x = concurrency.make_lock("test.X")
    y = concurrency.make_lock("test.Y")
    release = threading.Event()

    def holder():
        with x:
            release.wait(2.0)

    t = threading.Thread(target=holder, daemon=True)
    t.start()
    time.sleep(0.05)
    got = []

    def contender():
        with y:
            with x:
                got.append(1)

    t2 = threading.Thread(target=contender, daemon=True)
    t2.start()
    time.sleep(0.3)
    release.set()
    t2.join(5.0)
    t.join(5.0)
    assert got == [1]
    kinds = [v["kind"] for v in concurrency.lockcheck_report()]
    assert "held-while-blocked" in kinds


def test_watchdog_reentrant_lock_not_self_edge(lockcheck):
    r = concurrency.make_rlock("test.R")
    with r:
        with r:
            pass
    assert concurrency.lockcheck_report() == []


def test_condition_over_checked_lock_wait_notify(lockcheck):
    cv = threading.Condition(concurrency.make_rlock("test.CV"))
    done = []

    def waiter():
        with cv:
            cv.wait(timeout=5.0)
            done.append(1)

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    time.sleep(0.1)
    with cv:
        cv.notify_all()
    t.join(5.0)
    assert done == [1]


def test_watchdog_same_class_instances_abba(lockcheck):
    """Two locks sharing a class-level NAME are still distinct graph
    nodes: q1->q2 vs q2->q1 is a real deadlock pair, not a self-edge."""
    q1 = concurrency.make_lock("Queue._lock")
    q2 = concurrency.make_lock("Queue._lock")

    def order(a, b):
        with a:
            with b:
                pass

    t = threading.Thread(target=order, args=(q1, q2), daemon=True)
    t.start()
    t.join()
    t = threading.Thread(target=order, args=(q2, q1), daemon=True)
    t.start()
    t.join()
    kinds = [v["kind"] for v in concurrency.lockcheck_report()]
    assert "order-inversion" in kinds


def test_watchdog_witness_site_is_user_frame(lockcheck):
    a = concurrency.make_lock("site.A")
    b = concurrency.make_lock("site.B")

    def ab():
        with a:
            with b:
                pass

    def ba():
        with b:
            with a:
                pass

    for fn in (ab, ba):
        t = threading.Thread(target=fn, daemon=True)
        t.start()
        t.join()
    (v,) = concurrency.lockcheck_report()
    # the witness must point at THIS file, not threading.py internals
    assert "test_analysis.py" in v["detail"], v
    assert "threading.py" not in v["detail"], v


def test_get_env_empty_value_means_unset(monkeypatch):
    from dmlc_tpu.base import get_env

    monkeypatch.setenv("DMLC_RETRY_MAX_S", "")
    assert get_env("DMLC_RETRY_MAX_S", 30.0) == 30.0
    monkeypatch.setenv("DMLC_ELASTIC", "")
    assert get_env("DMLC_ELASTIC", True) is True
    # str knobs keep the empty string (callers use `or fallback`)
    monkeypatch.setenv("DMLC_TRACKER_URI", "")
    assert get_env("DMLC_TRACKER_URI", "x") == ""


def test_bufferpool_clean_under_lockcheck(lockcheck):
    pool = concurrency.BufferPool(lambda: object(), capacity=2)
    a = pool.acquire()
    b = pool.acquire()
    pool.release(a)
    pool.release(b)
    pool.kill()
    assert pool.acquire() is None
    concurrency.lockcheck_assert_clean()
