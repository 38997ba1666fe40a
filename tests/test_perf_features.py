"""§Perf feature correctness: flash custom-VJP vs oracle (fwd+grad),
sequence parallelism, local MoE dispatch, 16-bit boundary reductions."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_ref import flash_attention_ref


def _r(shape, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


@pytest.mark.parametrize("case", [
    dict(sq=64, sk=64, causal=True, window=None),    # triangular schedule
    dict(sq=64, sk=64, causal=True, window=16),      # banded (SWA)
    dict(sq=33, sk=33, causal=True, window=None),    # ragged tail
    dict(sq=64, sk=64, causal=False, window=None),   # full pairs
    dict(sq=1, sk=40, causal=True, window=None),     # decode alignment
    dict(sq=16, sk=48, causal=True, window=None),    # right-aligned chunk
])
def test_flash_forward_vs_oracle(case):
    q = _r((2, 3, case["sq"], 16), 1)
    k = _r((2, 3, case["sk"], 16), 2)
    v = _r((2, 3, case["sk"], 16), 3)
    out = flash_attention_ref(q, k, v, case["causal"], case["window"],
                              None, 32)
    want = jax.vmap(jax.vmap(functools.partial(
        ref.attention, causal=case["causal"], window=case["window"])))(
            q, k, v)
    np.testing.assert_allclose(out, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("window", [None, 12])
def test_flash_grads_vs_oracle_autodiff(window):
    q, k, v = _r((1, 2, 48, 8), 5), _r((1, 2, 48, 8), 6), _r((1, 2, 48, 8), 7)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention_ref(q, k, v, True, window, None,
                                           16) ** 2)

    def loss_ref(q, k, v):
        o = jax.vmap(jax.vmap(functools.partial(
            ref.attention, causal=True, window=window)))(q, k, v)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-3)


def test_flash_triangular_skips_masked_blocks():
    """The causal schedule must enumerate ~half the block pairs."""
    from repro.kernels.flash_ref import _pairs
    qi, kj = _pairs(8, 8, causal=True, aligned=True, wband=None)
    assert len(qi) == 8 * 9 // 2              # Q(Q+1)/2
    qi, kj = _pairs(8, 8, causal=True, aligned=True, wband=1)
    assert len(qi) == 1 + 7 * 2               # banded: ≤2 blocks per row
    qi, kj = _pairs(4, 8, causal=False, aligned=False, wband=None)
    assert len(qi) == 32                      # full grid


def test_seq_parallel_matches_baseline(run8):
    run8("""
import jax, numpy as np
from jax.sharding import AxisType
from repro.models import registry
from repro.core import lanes
from repro.runtime import Trainer, TrainConfig
from repro.data import make_pipeline
from repro.configs.base import ShapeConfig

mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,)*2)
shape = ShapeConfig("tiny", 64, 4, "train")
losses = {}
for name, rules in [("base", lanes.LogicalRules()),
                    ("sp", lanes.with_rules(seq_tp=("model",)))]:
    b = registry.build("llama3.2-3b", reduced=True, rules=rules)
    tr = Trainer(b.model, mesh, TrainConfig(num_steps=2, log_every=1,
                                            peak_lr=1e-3), rules=rules)
    st = tr.run(make_pipeline(b.cfg, shape, num_steps=2))
    losses[name] = [h["loss"] for h in st["_history"]]
np.testing.assert_allclose(losses["base"], losses["sp"], rtol=1e-4)
print("OK")
""", timeout=1200)


def test_moe_local_dispatch_matches_global(run8):
    run8("""
import jax, numpy as np
from jax.sharding import AxisType
from repro.models import registry, moe
from repro.runtime import Trainer, TrainConfig
from repro.data import make_pipeline
from repro.configs.base import ShapeConfig

mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,)*2)
shape = ShapeConfig("tiny", 64, 8, "train")
losses = {}
for mode in ["global", "local"]:
    moe.set_moe_dispatch(mode)
    b = registry.build("qwen3-moe-30b-a3b", reduced=True)
    tr = Trainer(b.model, mesh, TrainConfig(num_steps=4, log_every=1,
                                            peak_lr=2e-3))
    st = tr.run(make_pipeline(b.cfg, shape, num_steps=4))
    losses[mode] = [h["loss"] for h in st["_history"]]
moe.set_moe_dispatch("global")
np.testing.assert_allclose(losses["global"], losses["local"], rtol=5e-2)
print("OK")
""", timeout=1200)


def test_tp_reduce_16bit_matches(run8):
    run8("""
import jax, numpy as np
from jax.sharding import AxisType
from repro.models import registry, layers
from repro.core import lanes
from repro.runtime import Trainer, TrainConfig
from repro.data import make_pipeline
from repro.configs.base import ShapeConfig

mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,)*2)
shape = ShapeConfig("tiny", 64, 4, "train")
losses = {}
try:
    for name, mode in [("auto", "auto"), ("rs16", "bf16_scatter")]:
        layers.set_tp_reduce(mode)
        rules = lanes.with_rules(seq_tp=("model",))
        b = registry.build("llama3.2-3b", reduced=True, rules=rules)
        tr = Trainer(b.model, mesh, TrainConfig(num_steps=2, log_every=1,
                                                peak_lr=1e-3), rules=rules)
        st = tr.run(make_pipeline(b.cfg, shape, num_steps=2))
        losses[name] = [h["loss"] for h in st["_history"]]
finally:
    layers.set_tp_reduce("auto")
np.testing.assert_allclose(losses["auto"], losses["rs16"], rtol=3e-2)
print("OK")
""", timeout=1200)
