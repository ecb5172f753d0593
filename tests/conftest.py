"""Test configuration: force an 8-device virtual CPU platform.

This is the TPU analog of the reference's mpirun-on-one-box testing
(tests/CMakeLists.txt:114-117 runs distributed tests with 1/2/4 ranks on a
single machine): XLA's host platform is split into 8 virtual devices so the
multi-chip sharding paths compile and execute without TPU hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# The facades place jax's persistent compile cache (utils/platform.
# configure_compile_cache).  The suite stays out of it: written cold it
# costs the CPU tests ~6% of their wall, and tier-1 has none to spare.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax  # noqa: E402  (after the environment above is in place)
import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    # the tier-1 command (ROADMAP.md) runs `-m 'not slow'`: heavy tests
    # past the 870 s budget opt out with this marker and still run in a
    # plain `pytest tests/`
    config.addinivalue_line(
        "markers",
        "slow: heavy tests excluded from the tier-1 time budget",
    )


@pytest.fixture(autouse=True)
def _seed():
    from kaminpar_tpu.utils import rng

    rng.set_seed(0)
    yield


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Drop compiled executables between test modules.

    The suite compiles thousands of CPU executables in one process; the
    accumulated JIT state eventually segfaulted XLA's CPU compiler mid-
    suite (reproducible at the same test, absent when the same tests run
    in a fresh process).  Clearing per module keeps the live-executable
    population bounded at a small recompile cost."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="session")
def rgg2d_files(tmp_path_factory):
    """The 1,024-node sample graph (the size and average degree of the
    reference's misc/rgg2d.metis), generated from a seed and written
    once a session in the three formats the IO tests compare."""
    from kaminpar_tpu.graphs.factories import make_rgg2d
    from kaminpar_tpu.io import write_metis, write_parhip

    root = tmp_path_factory.mktemp("sample")
    g = make_rgg2d(1024, avg_degree=8, seed=1)
    write_metis(g, str(root / "rgg2d.metis"))
    write_parhip(g, str(root / "rgg2d-32bit.parhip"), use_32bit=True)
    write_parhip(g, str(root / "rgg2d-64bit.parhip"), use_32bit=False)
    return root


@pytest.fixture(scope="session")
def rgg2d_path(rgg2d_files):
    return str(rgg2d_files / "rgg2d.metis")


@pytest.fixture
def rgg2d(rgg2d_path):
    from kaminpar_tpu.io import load_graph

    return load_graph(rgg2d_path)
