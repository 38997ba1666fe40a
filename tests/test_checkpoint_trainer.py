"""Fault tolerance: checkpoint atomicity/retention/resharding, trainer
restart-equivalence, straggler detection, elastic re-mesh."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, latest_step, restore_pytree, \
    save_pytree
from repro.configs.base import ShapeConfig
from repro.data import make_pipeline
from repro.launch.mesh import make_test_mesh
from repro.models import registry
from repro.runtime import StragglerMonitor, Trainer, TrainConfig
from repro.runtime.elastic import elastic_remesh


# ---------------------------------------------------------------------------
# checkpoint store
# ---------------------------------------------------------------------------

def _state():
    return {"params": {"w": jnp.arange(12.0).reshape(3, 4)},
            "opt": {"step": jnp.asarray(7, jnp.int32)}}


def test_save_restore_roundtrip(tmp_path):
    path = str(tmp_path / "s.ckpt")
    st = _state()
    save_pytree(path, st, meta={"step": 7})
    template = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            st)
    out, meta = restore_pytree(path, template)
    assert meta["step"] == 7
    np.testing.assert_array_equal(out["params"]["w"], st["params"]["w"])
    assert out["opt"]["step"].dtype == jnp.int32


def test_restore_shape_mismatch_raises(tmp_path):
    path = str(tmp_path / "s.ckpt")
    save_pytree(path, _state())
    bad = {"params": {"w": jax.ShapeDtypeStruct((4, 4), jnp.float32)},
           "opt": {"step": jax.ShapeDtypeStruct((), jnp.int32)}}
    with pytest.raises(ValueError):
        restore_pytree(path, bad)


def test_manager_atomicity_ignores_incomplete(tmp_path):
    root = str(tmp_path)
    mgr = CheckpointManager(root, keep=5, async_write=False)
    mgr.save(10, _state())
    # a crashed half-write: directory without _COMPLETE
    os.makedirs(os.path.join(root, "step_20"))
    with open(os.path.join(root, "step_20", "state.ckpt"), "wb") as f:
        f.write(b"garbage")
    assert latest_step(root) == 10


def test_manager_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_write=False)
    for s in (1, 2, 3, 4):
        mgr.save(s, _state())
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(str(tmp_path))
                   if d.startswith("step_"))
    assert steps == [3, 4]


def test_manager_async_write_and_wait(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3, async_write=True)
    mgr.save(5, _state())
    mgr.wait()
    assert latest_step(str(tmp_path)) == 5
    st, meta, step = mgr.restore_latest(
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                     _state()))
    assert step == 5
    mgr.close()


# ---------------------------------------------------------------------------
# trainer restart equivalence
# ---------------------------------------------------------------------------

def _mk_trainer(tcfg):
    mesh = make_test_mesh((1, 1), ("data", "model"))
    bundle = registry.build("llama3.2-3b", reduced=True)
    return bundle, Trainer(bundle.model, mesh, tcfg)


def test_restart_resumes_identically(tmp_path):
    """kill-at-step-k + restart == uninterrupted run (data is step-pure,
    checkpoints are atomic).  Loss trajectories must match closely."""
    shape = ShapeConfig("tiny", 32, 4, "train")
    ck = str(tmp_path / "ck")

    # uninterrupted 6-step run
    tcfg_a = TrainConfig(num_steps=6, log_every=1, peak_lr=1e-3, seed=0)
    bundle, tr_a = _mk_trainer(tcfg_a)
    hist_a = tr_a.run(make_pipeline(bundle.cfg, shape, num_steps=6))[
        "_history"]

    # interrupted at step 3 (ckpt_every=3) then restarted
    tcfg_b = TrainConfig(num_steps=3, log_every=1, peak_lr=1e-3, seed=0,
                         ckpt_dir=ck, ckpt_every=100)
    bundle, tr_b = _mk_trainer(tcfg_b)
    tr_b.run(make_pipeline(bundle.cfg, shape, num_steps=3))
    tr_b._ckpt.wait()

    tcfg_c = TrainConfig(num_steps=6, log_every=1, peak_lr=1e-3, seed=0,
                         ckpt_dir=ck, ckpt_every=100)
    bundle, tr_c = _mk_trainer(tcfg_c)
    state, start = tr_c.maybe_restore()
    assert start == 3
    hist_c = tr_c.run(
        make_pipeline(bundle.cfg, shape, start_step=3, num_steps=3),
        start_step=start, state=state)["_history"]

    a = {h["step"]: h["loss"] for h in hist_a}
    c = {h["step"]: h["loss"] for h in hist_c}
    for s in (3, 4, 5):
        np.testing.assert_allclose(c[s], a[s], rtol=1e-4)


def test_straggler_monitor():
    mon = StragglerMonitor(slack=2.0, alpha=0.5)
    for step in range(5):
        assert not mon.observe(step, 1.0)
    assert mon.observe(5, 3.0)              # 3x the EWMA -> flagged
    assert mon.events[0][0] == 5
    assert not mon.observe(6, 1.1)          # EWMA not poisoned by straggler


def test_elastic_remesh_roundtrip():
    """State moves across meshes with different axis sizes; values intact."""
    mesh_a = make_test_mesh((1, 1), ("data", "model"))
    mesh_b = make_test_mesh((1,), ("data",))
    state = {"w": jnp.arange(64.0).reshape(8, 8)}

    def shardings_fn(st, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.tree.map(
            lambda _: NamedSharding(mesh, P()), st)

    moved = elastic_remesh(state, mesh_b, shardings_fn)
    np.testing.assert_array_equal(np.asarray(moved["w"]),
                                  np.asarray(state["w"]))


# ---------------------------------------------------------------------------
# distributed trainer (subprocess, 8 devices): all reduction modes agree
# ---------------------------------------------------------------------------

def test_reduction_modes_agree(run8):
    run8("""
import jax, numpy as np
from jax.sharding import AxisType
from repro.models import registry
from repro.runtime import Trainer, TrainConfig
from repro.data import make_pipeline
from repro.configs.base import ShapeConfig

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                 axis_types=(AxisType.Auto,)*3)
b = registry.build("llama3.2-3b", reduced=True)
shape = ShapeConfig("tiny", 32, 8, "train")
losses = {}
for mode in ["gspmd", "hier", "hier_tree", "hier_ef8"]:
    tcfg = TrainConfig(num_steps=2, log_every=1, reduction=mode,
                       peak_lr=1e-3, seed=0)
    tr = Trainer(b.model, mesh, tcfg)
    state = tr.run(make_pipeline(b.cfg, shape, num_steps=2))
    losses[mode] = [h["loss"] for h in state["_history"]]
np.testing.assert_allclose(losses["gspmd"], losses["hier"], rtol=1e-4)
np.testing.assert_allclose(losses["gspmd"], losses["hier_tree"], rtol=1e-4)
np.testing.assert_allclose(losses["gspmd"], losses["hier_ef8"], rtol=2e-2)
print("OK")
""", timeout=1200)
