import os
import sys

# repo-root imports (benchmarks package) in addition to PYTHONPATH=src
ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

# The distributed tests run IN-PROCESS on fake host devices, so the device
# count must be forced before the JAX backend initializes — i.e. before any
# test module (or conftest) triggers a computation. pyproject.toml documents
# this; pytest has no built-in env mechanism, so the suite-wide setting lives
# here, ahead of the first jax import.
from repro import compat  # noqa: E402

compat.ensure_host_devices(8)
# No persistent compilation cache for the suite: its fixed home is inside the
# checkout (compat.CACHE_DIR), and the checkout is what gets copied to the
# chip machine, so test runs must not grow it. A cache named by
# JAX_COMPILATION_CACHE_DIR in the environment is still honoured by JAX.

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def key():
    return compat.prng_key(0)


def pytest_sessionfinish(session, exitstatus):
    """Opt-in compile-cost report for the suite itself: run with
    ``REPRO_COMPILE_LEDGER=1`` and every Runtime.train_step compile the
    tests trigger is tallied into ``results/compile_ledger.json``
    (trace/compile wall seconds + hit/miss per executable key)."""
    from repro.obs import ledgers

    if not ledgers.global_active():
        return
    out = os.path.join(ROOT, "results", "compile_ledger.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    ledgers.GLOBAL_COMPILE_LEDGER.write(out)
    summ = ledgers.GLOBAL_COMPILE_LEDGER.summary()
    print(f"\n[obs] compile ledger -> {out}: {summ['compiles']} compile(s), "
          f"{summ['hits']} hit(s), {summ['total_compile_s']:.1f}s compiling")
