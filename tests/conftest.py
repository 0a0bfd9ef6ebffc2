"""Test bootstrap: force an 8-device virtual CPU platform BEFORE jax import.

Mirrors the reference's "distributed tests without a cluster" strategy
(local[N] SparkContext, SURVEY.md section 4.4): multi-chip behaviour is
exercised on 8 virtual CPU devices via
``--xla_force_host_platform_device_count``.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np
import pytest

import jax

# Tests are hermetic CPU and never touch a chip.  The variable pinned above
# is enough when this file is what first imports jax; the update also
# covers a plugin that imported it earlier.
jax.config.update("jax_platforms", "cpu")

# Golden tests compare against torch fp32; disable any reduced-precision
# matmul path (the perf path opts into bf16 explicitly instead).
jax.config.update("jax_default_matmul_precision", "highest")


#: quick-start tier (`pytest -m smoke`, <5 min): one representative module
#: per layer of SURVEY.md section 1 -- layers, conv, recurrent, optim,
#: end-to-end training, data pipeline, distributed (tp), importers, keras
#: facade, quantized engine.  The full suite stays the CI gate.
SMOKE_MODULES = {
    "test_layers.py", "test_conv.py", "test_recurrent.py", "test_optim.py",
    "test_training.py", "test_datasets.py", "test_tp.py",
    "test_tensorflow_interop.py", "test_keras_backend_compat.py",
    "test_quantized.py",
}


def pytest_collection_modifyitems(config, items):
    seen = set()
    for item in items:
        base = os.path.basename(str(item.fspath))
        if base in SMOKE_MODULES:
            seen.add(base)
            # slow-marked tests (convergence E2Es) stay out of the quick tier
            if item.get_closest_marker("slow") is None:
                item.add_marker(pytest.mark.smoke)
    # a renamed/deleted module must fail collection, not silently shrink
    # the smoke tier (full-suite runs collect every module)
    if len(items) > 500:
        missing = SMOKE_MODULES - seen
        assert not missing, f"SMOKE_MODULES entries not collected: {missing}"


@pytest.fixture(autouse=True)
def _seed():
    from bigdl_tpu.utils.random_generator import RNG

    RNG.set_seed(42)
    np.random.seed(42)
    yield
