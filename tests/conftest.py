"""Test config: force an 8-device virtual CPU mesh (SURVEY.md §4 tier-3 —
the reference's DistributedQueryRunner boots a fake multi-node cluster in
one JVM; we boot a fake 8-chip mesh in one process)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax  # noqa: E402

# belt and braces with the env var above: the config value, set before
# jax's first use, is what the backend lookup reads
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: tier-2 tests excluded from the tier-1 `-m 'not slow'` run")


@pytest.fixture(scope="session")
def tpch_catalog_tiny():
    from presto_tpu.catalog import tpch_catalog

    return tpch_catalog(sf=0.01, cache_dir="/tmp/presto_tpu_cache")


@pytest.fixture(scope="session")
def tpch_sqlite_tiny():
    """sqlite database loaded with the same SF0.01 TPC-H data (the
    reference's H2QueryRunner differential-oracle role)."""
    from tests.sqlite_oracle import build_sqlite

    return build_sqlite(sf=0.01)


@pytest.fixture(autouse=True, scope="module")
def _bound_suite_memory():
    """One-process full-suite runs accumulate XLA executables and
    device-column caches per module until the host OOMs (observed at
    ~119GB around the late tpcds modules).  Releasing both between
    modules bounds RSS; later modules recompile/re-upload lazily."""
    yield
    import gc

    import jax as _jax

    from presto_tpu.catalog import release_device_caches
    from presto_tpu.exec import compile_cache

    release_device_caches()
    compile_cache.clear()  # executable memo would pin what jax frees
    _jax.clear_caches()
    gc.collect()


@pytest.fixture
def lowered_texts(monkeypatch):
    """Every program the engine AOT-compiles, as lowered text with debug
    info, in build order."""
    from presto_tpu.exec import compile_cache as CC

    texts = []
    real = CC.Executable.aot_compile

    def spy(self, example_args):
        shapes = jax.tree_util.tree_map(CC._shape_struct, example_args)
        texts.append(self._jitted.lower(*shapes).as_text(debug_info=True))
        return real(self, example_args)

    monkeypatch.setattr(CC.Executable, "aot_compile", spy)
    return texts
