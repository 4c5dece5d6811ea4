"""Sharding rules validated on a real (small) mesh.

The 8-device execution check runs in-process when the suite was started
with ``REPRO_FORCE_HOST_DEVICES=8`` (the CI multidevice job — see
tests/conftest.py), and otherwise re-execs the same check in a child
interpreter with the device-forcing flag passed through its environment,
so the default single-device pytest process never mutates its own
``XLA_FLAGS``."""
import inspect
import os
import subprocess
import sys

import pytest

import jax

from repro.configs import base as cb
from repro.distributed.sharding import param_pspec


class _FakeMesh:
    axis_names = ("data", "model")
    shape = {"data": 16, "model": 16}


@pytest.mark.parametrize("path,shape,expect", [
    ("embed", (49280, 1024), ("model", "data")),
    ("head", (1024, 49280), ("data", "model")),
    ("stack/0/attn/wq", (24, 1024, 1024), (None, "data", "model")),
    ("stack/0/attn/wo", (24, 1024, 1024), (None, "model", "data")),
    ("stack/0/mlp/w_gate", (24, 1024, 512), (None, "data", "model")),
    # granite experts: E=32 divisible by model=16 -> expert parallelism
    ("stack/0/w_gate", (24, 32, 1024, 512), (None, "model", "data", None)),
    # mixtral experts: E=8 not divisible -> TP inside experts
    ("stack/0/w_up", (56, 8, 6144, 16384), (None, None, "data", "model")),
    ("stack/0/ln1/scale", (24, 1024), (None, None)),
    # vocab NOT divisible: guard drops the axis
    ("embed_odd", (49155, 1024), (None, "data")),
])
def test_param_rules(path, shape, expect):
    cfg = cb.get("granite-moe-1b-a400m")
    name = "embed" if path == "embed_odd" else path
    spec = param_pspec(name, shape, cfg, _FakeMesh())
    assert tuple(spec) == expect, (path, tuple(spec))


def _sharded_check():
    # Self-contained (shipped to a child interpreter via getsource when the
    # parent has fewer than 8 devices): train + decode one smoke step on a
    # real (2, 2, 2) pod/data/model mesh.
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import base as cb
    from repro.distributed import act, sharding as sh
    from repro.launch.mesh import make_test_mesh
    from repro.models.transformer import build_model

    mesh = make_test_mesh(2, 2, multi_pod=True)   # (2,2,2) pod/data/model
    cfg = cb.get("granite-moe-1b-a400m", smoke=True)
    model = build_model(cfg, policy="bf16", remat=False)
    params = model.init(jax.random.PRNGKey(0))
    shard = sh.params_shardings(params, cfg, mesh)
    params = jax.tree_util.tree_map(jax.device_put, params, shard)
    batch = {"tokens": jnp.zeros((4, 17), jnp.int32)}
    bshard = sh.batch_shardings(batch, mesh)
    batch = jax.tree_util.tree_map(jax.device_put, batch, bshard)
    with mesh, act.use_mesh(mesh):
        loss = jax.jit(model.loss_fn)(params, batch)
    assert np.isfinite(float(loss)), loss
    # decode path on the mesh
    caches = model.init_caches(4, 32)
    cshard = sh.caches_shardings(jax.eval_shape(lambda: caches), cfg, mesh)
    caches = jax.tree_util.tree_map(jax.device_put, caches, cshard)
    with mesh, act.use_mesh(mesh):
        logits, caches = jax.jit(model.decode_step)(
            params, jnp.zeros((4, 1), jnp.int32), caches, jnp.int32(0))
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    print("SHARDED_OK", float(loss))


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="in-process variant needs 8 devices "
                           "(REPRO_FORCE_HOST_DEVICES=8)")
def test_sharded_execution_8dev_inprocess():
    _sharded_check()


@pytest.mark.skipif(jax.device_count() >= 8,
                    reason="covered by the in-process variant")
def test_sharded_execution_8dev_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    # The parent holds the process's accelerator (if any); the child gets
    # its eight devices from the host platform and never asks for a chip.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    child = inspect.getsource(_sharded_check) + "\n_sharded_check()\n"
    out = subprocess.run([sys.executable, "-c", child], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SHARDED_OK" in out.stdout, out.stdout[-2000:] + out.stderr[-2000:]
