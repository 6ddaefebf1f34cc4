"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware (the driver separately dry-runs the
multi-chip path; see ``__graft_entry__.dryrun_multichip``). Env vars must be
set before jax initializes its backends, hence the top-of-file placement.

This mirrors the reference's harness pattern of a strict base test class
(``BaseDL4JTest`` setting SCOPE_PANIC profiling,
``deeplearning4j-core/src/test/java/org/deeplearning4j/BaseDL4JTest.java:8``):
here we enable jax's strongest always-on checks instead.
"""

import os

# Tests run on the virtual 8-device CPU mesh whatever the ambient
# environment selects (on a chip machine JAX defaults to the TPU).
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# the variable is read when jax is first imported; the config update
# also holds when a pytest plug-in imported jax before this file
jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: repeat suite runs skip recompilation of
# unchanged jitted programs (SURVEY §4 fast-tier mandate).
#
# The cache dir is KEYED BY A HOST-CPU FINGERPRINT: XLA:CPU AOT results
# embed the compile machine's feature set, and executing an entry cached
# on a different machine can raw-SIGABRT/SIGILL ("Loading XLA:CPU AOT
# result. Target machine feature ... not supported on the host machine
# ... could lead to execution errors such as SIGILL"). Round-4 bisect:
# a 39 MB cache carried over from another host made the MoE EP+SP step
# abort on every cache hit, looking like a heisenbug in whatever test
# ran it first.


def _host_cache_tag() -> str:
    import hashlib
    import platform

    try:
        with open("/proc/cpuinfo") as f:
            feat = next(l for l in f if l.startswith("flags"))
    except (OSError, StopIteration):
        feat = platform.processor() or platform.machine()
    return hashlib.sha256(feat.encode()).hexdigest()[:12]


# JAX_COMPILATION_CACHE_DIR places the cache from outside (JAX reads it
# itself; nothing is set here then).
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(__file__), ".jax_cache",
                     _host_cache_tag()))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

# NaN debugging is opt-in per test (jax.debug_nans breaks some valid ops);
# keep x64 off to match TPU numerics, tests that need fp64 enable it locally.
jax.config.update("jax_threefry_partitionable", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="run tests marked @pytest.mark.slow (heavy-integration tier)",
    )


def pytest_collection_modifyitems(config, items):
    """Two-tier suite mirroring the reference's fast-unit vs
    heavy-integration split (SURVEY §4): @slow tests only run with
    --runslow or RUN_SLOW=1."""
    if config.getoption("--runslow") or os.environ.get("RUN_SLOW") == "1":
        return
    skip = pytest.mark.skip(reason="slow tier: pass --runslow or RUN_SLOW=1")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture
def no_persistent_cache():
    """Compile afresh: run one test with the persistent compilation
    cache off. An XLA:CPU executable RELOADED from the cache can run its
    collectives in another order than its peers expect (jaxlib 0.9: the
    bf16 ring-attention step over data x seq passes when it compiles and
    aborts at a rendezvous time-out on every later, warm run)."""
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture
def rng():
    return jax.random.PRNGKey(12345)


@pytest.fixture
def np_rng():
    return np.random.default_rng(12345)
