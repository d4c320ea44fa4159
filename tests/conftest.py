"""Test harness: 8 fake CPU devices — the reference's
"multi-process-without-a-cluster" test mode (SURVEY.md §4 implication (b)),
TPU-native style: pmap/pjit/shard_map collectives run unmodified on a
virtual 8-device mesh, so distributed semantics are unit-testable anywhere.
"""

import os
import sys

# Must run before jax initializes its backends (conftest imports precede
# test-module imports under pytest). Set in the environment so subprocess
# tests inherit the same CPU platform and device count.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent XLA compilation cache, shared by every test AND every
# subprocess test (they inherit the env): the suite is compile-dominated,
# and a warm cache measured 1.8x on the heaviest file. Keyed by HLO +
# compile options, so stale-cache wrongness is not a failure mode; safe to
# delete any time. Override by exporting JAX_COMPILATION_CACHE_DIR to
# another path; export it EMPTY to disable entirely (mapped to
# JAX_ENABLE_COMPILATION_CACHE=0 below — jax itself would treat '' as a
# cwd-relative cache dir, not as off).
#
# CAVEAT — killed children: a subprocess test that SIGKILLs/os._exit()s a
# training child (resume/fault-injection e2e) can tear or race a cache
# write, and a poisoned entry later deserializes into a
# SEGFAULT or a silently WRONG executable (observed: an EMA shadow off by
# exactly the decay factor). Tests that kill children mid-run must set
# JAX_ENABLE_COMPILATION_CACHE=0 in the child env (the supervisor/fault
# tests do); if an inexplicable numeric failure appears after such runs,
# delete this cache dir first.
if os.environ.get("JAX_COMPILATION_CACHE_DIR") == "":
    del os.environ["JAX_COMPILATION_CACHE_DIR"]
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"
elif os.environ.get("JAX_ENABLE_COMPILATION_CACHE") != "0":
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(os.path.dirname(os.path.abspath(__file__)), ".jax_cache"),
    )
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

import pytest  # noqa: E402

from horovod_tpu.testing import cachecheck  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running end-to-end tests")
    config.addinivalue_line(
        "markers", "ci_job: full CI-gated convergence runs (several minutes)"
    )
    # Guard 1 for the twice-documented poisoned-cache failure mode (the
    # CAVEAT above): a zero-byte or orphaned-.tmp cache entry is
    # definitionally torn (its atomic rename never completed) — delete it
    # before it can deserialize into a SEGFAULT or a silently wrong
    # executable mid-suite.
    removed = cachecheck.remove_torn_entries(
        cachecheck.cache_dir_from_env()
    )
    if removed:
        print(
            f"\n[conftest] removed {len(removed)} torn persistent-XLA-"
            f"cache entr{'y' if len(removed) == 1 else 'ies'} "
            "(zero-byte/.tmp — a killed child interrupted the write):\n"
            + "\n".join(f"  {p}" for p in removed)
        )


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """Guard 2: when a test fails with the torn-cache deserialization
    signature, attach the actionable `rm -rf tests/.jax_cache` hint to
    the report instead of leaving the operator to chase phantom numeric
    mismatches (the documented PR 5/PR 8 time sink)."""
    outcome = yield
    report = outcome.get_result()
    if report.when != "call" or not report.failed:
        return
    advice = cachecheck.poisoned_cache_advice(
        str(report.longrepr), cachecheck.cache_dir_from_env()
    )
    if advice:
        report.sections.append(("poisoned XLA cache?", advice))


@pytest.fixture(scope="session")
def tmp_cache(tmp_path_factory):
    d = tmp_path_factory.mktemp("hvt_cache")
    os.environ["HVT_DATA_DIR"] = str(d)
    return d


# --- the described chip of the compile tests, which alone request it -------
# (tests/test_chip_compile.py, tests/test_kernel_compile.py)

@pytest.fixture(scope="module")
def topo():
    """A described TPU v5e:2x2 (`horovod_tpu.testing.described_v5e`),
    described inside a fixture and never at import, so every xdist worker
    collects the same tests. Where libtpu cannot describe it (no libtpu, or
    a second process without ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``) the module's
    tests skip with libtpu's reason."""
    from horovod_tpu.testing import described_v5e

    try:
        chip = described_v5e.described()
        desc = chip.__enter__()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    try:
        yield desc
    finally:
        chip.__exit__(None, None, None)


@pytest.fixture
def compiled_kernel(monkeypatch):
    """Steer the program's one interpret decision to what it takes on the
    chip. It asks the attached devices, which are CPUs here."""
    from horovod_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "default_interpret", lambda: False)
