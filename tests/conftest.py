"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests and benches must
see the single real CPU device; only launch/dryrun.py forces 512."""

import contextlib
import faulthandler
import signal
import sys
import warnings

import jax
import pytest
from threadpoolctl import threadpool_limits

warnings.filterwarnings("ignore")

# One BLAS thread per process: under `-n 6` each worker's OpenBLAS pool
# spin-waits for cores the other workers hold, and a dense linear-algebra
# test then runs tens of times slower than alone.  numpy is loaded before
# this file, so its BLAS takes the cap here, not from OPENBLAS_NUM_THREADS.
threadpool_limits(1, user_api="blas")

# Energy-baseline gates (assert_no_energy_regression / energy_gate /
# the `energy_regression` marker) come from the in-tree plugin.
pytest_plugins = ["repro.testing.pytest_plugin", "pytester"]

# Each phase (setup, call, teardown) of a test gets this many seconds unless
# it carries @pytest.mark.timeout(seconds).  An overrun prints every
# thread's stack and fails the test; the run goes on with the next one.
# A phase stuck in native code cannot take the alarm until that code
# returns; faulthandler prints its stacks STUCK_GRACE_S after the limit.
DEFAULT_TIMEOUT_S = 300.0
STUCK_GRACE_S = 30.0


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    with _time_limit(item):
        yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    with _time_limit(item):
        yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    with _time_limit(item):
        yield


@contextlib.contextmanager
def _time_limit(item):
    mark = item.get_closest_marker("timeout")
    limit = float(mark.args[0]) if mark else DEFAULT_TIMEOUT_S

    def expire(signum, frame):
        faulthandler.dump_traceback(file=sys.stderr)
        pytest.fail(f"Timeout: over its {limit:g} s limit "
                    "(every thread's stack is in the captured stderr)")

    faulthandler.dump_traceback_later(limit + STUCK_GRACE_S, file=sys.stderr)
    prev = signal.signal(signal.SIGALRM, expire)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, prev)
        faulthandler.cancel_dump_traceback_later()


@pytest.fixture(scope="session")
def key():
    return jax.random.key(0)


class _LazyGoldenRecords:
    """Per-case lazily recorded golden baselines (mapping-like).

    Each case is recorded on first access and cached for the session, so a
    ``-k``-selected subset (the CI fast lane runs backend parity on four
    cases) only pays for the cases it touches.
    """

    def __init__(self, store):
        from repro.zoo import cases as zoo
        self._store = store
        self._zoo = zoo
        self._cache = {}

    def __getitem__(self, case_id):
        if case_id not in self._cache:
            res = self._store.record(self._zoo.get_case(case_id))
            self._cache[case_id] = {
                "baseline": res.baseline,
                "report": res.report,
                "graph_a": res.art_a.graph,
                "graph_b": res.art_b.graph,
            }
        return self._cache[case_id]


@pytest.fixture(scope="session")
def golden(tmp_path_factory):
    """Golden baselines for the zoo, recorded lazily per case.

    Cases are recorded into a fresh BaselineStore (artifacts +
    committed-style JSON under a session tmp dir) on first access through
    ``golden["records"][case_id]``; the lightweight record-time products —
    baseline, report, both traced graphs — are kept for downstream suites
    (offline drift replay, backend parity), which are parametrised per
    case; each xdist worker records, in its own session, the cases its
    files touch.  The heavy CandidateArtifacts are dropped; their bytes
    live in the store on disk.
    """
    from repro.testing.baselines import BaselineStore

    import shutil

    root = tmp_path_factory.mktemp("golden-baselines")
    store = BaselineStore(root)
    yield {"root": root, "records": _LazyGoldenRecords(store)}
    # the artifact store is multi-GB; don't let pytest's retained tmp dirs
    # (default: last 3 sessions) accumulate it in /tmp
    shutil.rmtree(root / "store", ignore_errors=True)
