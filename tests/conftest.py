"""Test harness: force JAX onto a virtual 8-device CPU mesh.

SURVEY.md §4: the build's test strategy is (1) deterministic mock-LLM
fixtures, (2) a CPU-jax path so the whole stack runs in CI without TPUs,
(3) multi-device simulation via ``xla_force_host_platform_device_count``.
Environment variables must be set before jax is first imported, hence the
module-level os.environ writes here.
"""

import os

# Force, don't setdefault: the environment may pin JAX_PLATFORMS to a real
# accelerator platform, and tests must be hermetic: they run on the CPU,
# on eight virtual devices.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# The same setting through jax's config: it holds even where jax was
# imported (and read the environment) before this file ran.
jax.config.update("jax_platforms", "cpu")

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402

# The suite compiles hundreds of XLA:CPU executables in one process; each
# holds mmap'd JIT code pages that are never unmapped while the jit cache
# holds the program. Measured: the process crosses vm.max_map_count
# (65530 default) around 350 tests and LLVM SEGFAULTS on the failed mmap
# mid-compile. Two defenses: raise the limit when we can (CI images run
# as root), and drop compiled programs between test modules — modules
# rarely share shapes, so the recompile cost is small and map growth
# stays bounded.
try:  # best-effort; harmless without privileges
    with open("/proc/sys/vm/max_map_count", "r+") as f:
        if int(f.read()) < 1_048_576:
            f.seek(0)
            f.write("1048576")
except OSError:
    pass


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    yield
    jax.clear_caches()

# pytest-asyncio is not available in this image; provide a minimal strict-mode
# equivalent: coroutine tests marked ``@pytest.mark.asyncio`` run under
# ``asyncio.run`` on a fresh event loop per test.


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: run coroutine test on an event loop")


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    test_fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(test_fn):
        sig_names = set(inspect.signature(test_fn).parameters)
        kwargs = {k: v for k, v in pyfuncitem.funcargs.items() if k in sig_names}
        asyncio.run(test_fn(**kwargs))
        return True
    return None
