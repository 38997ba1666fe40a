"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests and benches must
see the real single CPU device; only the dry-run (and subprocess-based
distributed tests) force a host device count."""
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_devices_subprocess(script: str, n_devices: int = 8,
                           timeout: int = 900) -> str:
    """Run ``script`` in a fresh python with n fake CPU devices."""
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}",
               PYTHONPATH=SRC)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={proc.returncode}):\n"
            f"--- stdout ---\n{proc.stdout[-4000:]}\n"
            f"--- stderr ---\n{proc.stderr[-4000:]}")
    return proc.stdout


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# tiny non-dense family configs, shared by the serving-path suites
# (test_chunked_prefill / test_zero_copy).  One source of truth —
# configs.base.tiny_family_configs — also feeds bench_serving's family
# claims, so the pinned regime cannot drift between tests and bench.
# ---------------------------------------------------------------------------

FAMILY_CFGS = None      # populated lazily so conftest import stays free of
                        # repro imports (collection works without PYTHONPATH)


def family_cfgs():
    global FAMILY_CFGS
    if FAMILY_CFGS is None:
        from repro.configs.base import tiny_family_configs
        FAMILY_CFGS = tiny_family_configs()
    return FAMILY_CFGS


@pytest.fixture(scope="module", params=("hybrid", "moe", "ssm"))
def family_model(request):
    """(cfg, model, params) per non-dense family — module-scoped so each
    suite reuses one initialised model per family."""
    from repro.models import registry
    cfg = family_cfgs()[request.param]
    model = registry.build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture
def run8():
    """Run a test script in a subprocess with 8 fake CPU devices."""
    def runner(script: str, n_devices: int = 8, timeout: int = 900):
        return run_devices_subprocess(script, n_devices, timeout)
    return runner
