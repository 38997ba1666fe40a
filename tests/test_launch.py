"""Entry-point plumbing: the compile-cache rule, replica placement on
devices, and the chip smoke's refusal to run off a TPU."""
import os
import sys

import jax
import pytest

from conftest import REPO

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_env_var(monkeypatch, restore_cache_dir):
    """Set: JAX reads the variable itself and nothing is configured."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch,
                                                      restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # the path is part of every cache key: calling again never moves it
    assert compile_cache.enable_compile_cache() == path
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_replica_refuses_several_devices():
    from repro.runtime.serving import EngineConfig, Replica
    with pytest.raises(ValueError, match="one device"):
        Replica(0, None, None, None, config=EngineConfig(),
                devices=["dev0", "dev1"])


def test_replicas_run_on_their_own_devices(run8):
    """Four replicas on a four-device host: each engine's arena is built on
    and lives on its own device, and the streams equal one engine's."""
    run8("""
import functools
import jax, numpy as np
from repro.launch.serve import make_engine, make_router
from repro.models import registry
from repro.runtime.serving import EngineConfig, Request, compare_streams

b = registry.build("llama3.2-3b", reduced=True)
params = jax.jit(b.model.init)(jax.random.PRNGKey(0))
cfg = EngineConfig(max_slots=2, max_seq=64, prefill_chunks=(8, 16))
rng = np.random.default_rng(0)
reqs = [Request(uid=i, prompt=rng.integers(0, b.cfg.vocab, 5 + 3 * i)
                .astype(np.int32), max_new_tokens=6) for i in range(8)]
eng = make_engine(b, params, config=cfg)
for r in reqs:
    eng.submit(r)
one = eng.run()

# where each arena is first built: never on the default device and copied
built = []
init_cache = b.model.init_cache
@functools.wraps(init_cache)
def spy(*a, **k):
    out = init_cache(*a, **k)
    built.append({d for leaf in jax.tree.leaves(out) for d in leaf.devices()})
    return out
b.model.init_cache = spy
for n in (4, 6):
    built.clear()
    router = make_router(b, params, config=cfg, replicas=n)
    # arena + batch-1 template per replica, each built on its own device
    assert built == [{jax.devices()[r % 4]} for r in range(n)
                     for _ in range(2)], built
    for r in reqs:
        router.submit(r)
    fleet = router.run()
    assert compare_streams(one, fleet).identical
    homes = [{d for leaf in jax.tree.leaves(rep.engine._cache)
              for d in leaf.devices()}
             for rep in router.replicas.values()]
    assert all(len(h) == 1 for h in homes), homes
    # replicas beyond the device count share devices (shards cycle)
    assert len(set().union(*homes)) == 4, homes
    assert [rep.devices for rep in router.replicas.values()][:4] == \\
        [[d] for d in jax.devices()]
print("OK")
""", n_devices=4)


def test_chip_smoke_refuses_without_tpu(capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("on a TPU host the smoke would run for real")
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "no TPU" in captured.err
