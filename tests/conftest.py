"""Shared fixtures: one seed to rule every test's randomness.

Determinism policy (tier-1 must be reproducible run-to-run): tests derive
ALL randomness — jax PRNG keys, numpy RandomStates, prompt contents — from
the `base_seed` fixture (or an explicit literal), never from entropy
sources. The `_hypothesis_compat` shim already seeds itself from the test's
qualified name, so property tests reproduce too.
"""

import os

# Simulated 2-device host platform for the mesh-sharded serving suite
# (tests/test_serve_sharded.py drives shard_map over a (data=2, model=1)
# mesh in-process). MUST run before the first jax import anywhere — jax
# locks the device count at first init; pytest imports conftest.py before
# any test module, so this is the one reliable hook. Every other test is
# device-count agnostic (unsharded computations land on device 0 and
# produce bit-identical results), and the multi-device subprocess tests
# (test_pipeline / test_substrate / test_dist) set their own flags.
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2").strip()
# Round every bf16 op to bf16. With excess precision allowed, XLA:CPU keeps
# bf16 intermediates in f32 inside a fusion, so where a result is rounded
# depends on how the program was fused: the shard_map'd serving step and the
# one-host step then differ by bf16 ulps (an MLA layer's residual + MLP),
# which MoE routing turns into different tokens. The bitwise suites compare
# differently fused programs, so they need the rounding pinned.
if "--xla_allow_excess_precision" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_allow_excess_precision=false"

import jax
import numpy as np
import pytest

BASE_SEED = 0

# Files whose every test is a Pallas-kernel parity check: the `kernels`
# marker (pytest.ini) is wired here by path, so `-m kernels` selects the
# whole contract suite (and `-m "not kernels"` skips interpret-mode Pallas
# on machines where it is slow) without per-file pytestmark boilerplate.
_KERNEL_SUITES = {"test_kernels.py", "test_paged_attention.py"}

# Distribution-layer suites (sharding rules, pipeline/compression shard_map
# programs, the mesh-sharded serving engine): `-m dist` selects them, wired
# by path like the kernel marker above.
_DIST_SUITES = {"test_dist.py", "test_pipeline.py", "test_serve_sharded.py"}

# Scheduler-policy suite (admission ordering, aging, prefill preemption):
# `-m scheduler` selects it, wired by path like the markers above.
_SCHED_SUITES = {"test_scheduler.py"}

# Observability suite (metrics registry, request tracing, engine telemetry,
# quantization-health probe): `-m obs` selects it, wired by path.
_OBS_SUITES = {"test_obs.py"}

# Quantized-KV-cache suite (NVFP4 cache codec, PackedKV pools, packed-operand
# decode kernels, kv_quant engine parity): `-m kvq` selects it, wired by path.
_KVQ_SUITES = {"test_kv_quant.py"}

# Streaming-frontend suites (asyncio HTTP/SSE server, engine-thread bridge,
# cancellation races): `-m frontend` selects them, wired by path. These
# tests get a hard per-test wall-clock guard (see `_frontend_timeout`) — a
# wedged stream or deadlocked thread boundary fails fast instead of hanging
# the whole tier-1 run.
_FRONTEND_SUITES = {"test_frontend.py", "test_cancel_races.py"}

# Hierarchical prefix-cache suite (host-RAM spill tier, swap-in, cross-shard
# replication, disaggregated handoff conservation): `-m tiered` selects it,
# wired by path. Shares the frontend suites' SIGALRM wall-clock guard — the
# fuzz walks and swap-in paths touch the same engine/pool machinery a
# deadlock would wedge.
_TIERED_SUITES = {"test_prefix_tiers.py"}

#: per-test wall-clock ceiling for the frontend suites, seconds. Generous —
#: normal tests finish in a few seconds even with XLA compiles; the guard
#: exists to catch deadlocks/hangs, not slowness.
FRONTEND_TEST_TIMEOUT_S = 180


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.fspath.basename in _KERNEL_SUITES:
            item.add_marker(pytest.mark.kernels)
        if item.fspath.basename in _DIST_SUITES:
            item.add_marker(pytest.mark.dist)
        if item.fspath.basename in _SCHED_SUITES:
            item.add_marker(pytest.mark.scheduler)
        if item.fspath.basename in _OBS_SUITES:
            item.add_marker(pytest.mark.obs)
        if item.fspath.basename in _KVQ_SUITES:
            item.add_marker(pytest.mark.kvq)
        if item.fspath.basename in _FRONTEND_SUITES:
            item.add_marker(pytest.mark.frontend)
            item.add_marker(pytest.mark.usefixtures("_frontend_timeout"))
        if item.fspath.basename in _TIERED_SUITES:
            item.add_marker(pytest.mark.tiered)
            item.add_marker(pytest.mark.usefixtures("_frontend_timeout"))


@pytest.fixture()
def _frontend_timeout():
    """SIGALRM-based per-test timeout for the frontend suites (no external
    timeout plugin in the image). Applied via marker wiring above, main
    thread only — SIGALRM interrupts a hung `asyncio.run` / `Event.wait`
    with a loud failure instead of wedging CI. No-op off-POSIX or when a
    previous alarm is pending (never clobber someone else's timer)."""
    import signal

    if (not hasattr(signal, "SIGALRM")
            or signal.getsignal(signal.SIGALRM) not in
            (signal.SIG_DFL, signal.SIG_IGN, None)):
        yield
        return

    def _fail(signum, frame):
        raise TimeoutError(
            f"frontend test exceeded {FRONTEND_TEST_TIMEOUT_S}s wall clock "
            "(deadlocked stream/bridge?)")

    old = signal.signal(signal.SIGALRM, _fail)
    signal.alarm(FRONTEND_TEST_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(scope="session")
def base_seed() -> int:
    return BASE_SEED


@pytest.fixture()
def base_key(base_seed):
    """Fresh jax PRNG key per test, derived from the shared seed."""
    return jax.random.PRNGKey(base_seed)


@pytest.fixture()
def np_rng(base_seed):
    """Fresh numpy RandomState per test, derived from the shared seed."""
    return np.random.RandomState(base_seed)
