"""Sharding-rule unit tests: logical axes, divisibility fitting, ZeRO-1,
cache specs, dispatch queue."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.core import dispatch, lanes
from repro.launch.mesh import make_test_mesh
from repro.models import partition, registry


def test_spec_drops_absent_mesh_axes():
    rules = lanes.LogicalRules(mesh_axes=("data", "model"))
    assert rules.spec("batch", None) == P("data", None)   # pod dropped
    rules3 = lanes.LogicalRules(mesh_axes=("pod", "data", "model"))
    assert rules3.spec("batch", None) == P(("pod", "data"), None)


def test_constrain_noop_without_mesh():
    x = jnp.ones((4, 4))
    out = lanes.constrain(x, lanes.LogicalRules(), "batch", "ffn")
    np.testing.assert_array_equal(out, x)


def test_param_logical_axes_dense():
    bundle = registry.build("llama3.2-3b", reduced=True)
    ap = jax.eval_shape(bundle.model.init, jax.random.PRNGKey(0))
    specs = partition.param_specs(ap)
    assert specs["embed"] == P("model", None)
    assert specs["lm_head"] == P(None, "model")
    assert specs["layers"]["attn"]["wq"] == P(None, None, "model")
    assert specs["layers"]["attn"]["wo"] == P(None, "model", None)
    assert specs["layers"]["mlp"]["w_up"] == P(None, None, "model")
    assert specs["layers"]["mlp"]["w_down"] == P(None, "model", None)
    assert specs["layers"]["ln1"]["scale"] == P(None, None)
    assert specs["final_norm"]["scale"] == P(None)


def test_param_logical_axes_moe_ssm():
    bundle = registry.build("qwen3-moe-30b-a3b", reduced=True)
    ap = jax.eval_shape(bundle.model.init, jax.random.PRNGKey(0))
    specs = partition.param_specs(ap)
    assert specs["layers"]["moe"]["experts"]["w_up"] == \
        P(None, "model", None, None)
    assert specs["layers"]["moe"]["router"] == P(None, None, None)

    bundle = registry.build("mamba2-2.7b", reduced=True)
    ap = jax.eval_shape(bundle.model.init, jax.random.PRNGKey(0))
    specs = partition.param_specs(ap)
    assert specs["layers"]["mamba"]["w_x"] == P(None, None, "model")
    assert specs["layers"]["mamba"]["w_out"] == P(None, "model", None)
    assert specs["layers"]["mamba"]["A_log"] == P(None, "model")


def test_fit_spec_divisibility():
    mesh = AbstractMesh((2, 2), ("data", "model"))
    # 50280 % 2 == 0 -> kept; 51 % 2 == 1 -> dropped
    assert partition.fit_spec(P("model", None), (50280, 64), mesh) == \
        P("model", None)
    assert partition.fit_spec(P("model", None), (51, 64), mesh) == \
        P(None, None)
    # tuple axes: keep the divisible prefix
    assert partition.fit_spec(P(("data", "model"),), (2,), mesh) == \
        P("data")


def test_zero1_spec_adds_data_only_when_divisible():
    mesh = AbstractMesh((2, 1), ("data", "model"))
    sp = partition.zero1_spec(P(None, "model"), (4096, 64), mesh)
    assert sp == P("data", "model")
    sp = partition.zero1_spec(P(None, None), (4097, 4096), mesh)
    assert sp == P(None, "data")           # first dim not divisible
    sp = partition.zero1_spec(P("data", None), (4096, 64), mesh)
    assert sp == P("data", None)           # data already used: unchanged


def test_cache_specs():
    """KV cache: batch over DP, *sequence* over lanes (flash-decode; the
    kv-heads option replicates for GQA — see lanes.DEFAULT_RULES)."""
    bundle = registry.build("qwen3-14b", reduced=True)
    cache = jax.eval_shape(lambda: bundle.model.init_cache(4, 64))
    specs = partition.cache_specs(cache)
    assert specs["k"] == P(None, ("pod", "data"), "model", None, None)
    mesh = make_test_mesh((1, 1), ("data", "model"))
    fitted = partition.cache_specs(cache, mesh=mesh)
    # every axis divides on a 1x1 mesh
    assert fitted["k"] == P(None, "data", "model", None, None)


def test_dispatch_queue_depth_and_drain():
    calls = []

    def step(x):
        calls.append(x)
        return jnp.asarray(x + 1.0)

    q = dispatch.DispatchQueue(step, depth=2)
    s = 0.0
    for _ in range(5):
        s = float(q.submit(s))
    q.drain()
    assert len(calls) == 5 and s == 5.0


def test_ideal_dispatcher_scan():
    run = dispatch.ideal_dispatcher(lambda s: s + 1.0, num_steps=10)
    out = run(jnp.zeros(()))
    assert float(out) == 10.0
