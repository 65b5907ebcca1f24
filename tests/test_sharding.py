"""Partition rules + small-mesh jit integration (subprocess: 4 devices)."""
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_reduced_config
from repro.models import build_model
from repro.parallel import sharding as sh

REPO = Path(__file__).resolve().parents[1]


def test_rules_cover_all_arch_params():
    """Every 2-D+ parameter of every arch must match a non-default rule or
    be a small vector (norms/biases). Catches renamed params silently
    falling to replicated."""
    rules = sh.default_param_rules(fsdp=True)
    for arch in ARCH_IDS:
        cfg = get_reduced_config(arch)
        model = build_model(cfg)
        shapes = jax.eval_shape(model.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
        leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
        for path, leaf in leaves:
            pstr = sh.tree_path_str(path)
            if leaf.ndim < 2 or min(leaf.shape[-2:]) < 16:
                continue
            matched = None
            import re

            for pat, template in rules:
                if re.fullmatch(pat, pstr):
                    matched = template
                    break
            assert matched is not None and matched != (), (arch, pstr, leaf.shape)


def test_spec_fit_drops_indivisible_axes():
    mesh_shape = {"data": 4, "model": 4}

    class FakeMesh:
        axis_names = tuple(mesh_shape)

        class devices:
            shape = tuple(mesh_shape.values())

    spec = sh._fit_spec(("data", "model"), (6, 16), FakeMesh)
    assert spec == jax.sharding.PartitionSpec(None, "model")
    spec = sh._fit_spec(("model",), (3, 8), FakeMesh)  # left-pad stacked dims
    assert spec == jax.sharding.PartitionSpec(None, "model")


def test_constrain_noop_without_mesh():
    sh.set_mesh_context(None)
    x = jnp.ones((4, 4))
    assert sh.constrain(x, "batch", None) is x


def test_batch_sharding_fallback_for_batch_one():
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, sys
sys.path.insert(0, "SRC")
from repro.launch.mesh import make_mesh
from repro.parallel import sharding as sh
mesh = make_mesh((2, 2), ("data", "model"))
s = sh.batch_sharding(mesh, jax.ShapeDtypeStruct((1, 8), jnp.int32), ("data",))
assert s.spec == jax.sharding.PartitionSpec(), s.spec
s = sh.batch_sharding(mesh, jax.ShapeDtypeStruct((4, 8), jnp.int32), ("data",))
assert s.spec == jax.sharding.PartitionSpec(("data",), None), s.spec
print("OK")
""".replace("SRC", str(REPO / "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr


def test_small_mesh_sharded_train_step_executes():
    """End-to-end: reduced olmo train step under a 2×2 mesh with the
    production partition rules — values must match the unsharded step."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np, sys
sys.path.insert(0, "SRC")
from repro.configs import get_reduced_config
from repro.configs.base import TrainConfig
from repro.models import build_model
from repro.parallel import sharding as sh
from repro.train.loop import init_train_state, make_train_step
from repro.data import DataIterator
from repro.launch.mesh import make_mesh

cfg = get_reduced_config("olmo-1b")
model = build_model(cfg)
tc = TrainConfig(lr=1e-3)
params = model.init(jax.random.PRNGKey(0))
state = init_train_state(params, tc)
batch = jax.tree_util.tree_map(jnp.asarray,
                               DataIterator(cfg, 4, 32, seed=0).batch_at(0))
step = make_train_step(model, tc)
_, m_ref = step(state, batch)

mesh = make_mesh((2, 2), ("data", "model"))
sh.set_mesh_context(mesh, ("data",))
pshard = sh.make_param_shardings(params, mesh, fsdp=True)
from repro.optim import adamw
state_sh = jax.device_put(state, type(state)(
    params=pshard,
    opt=adamw.AdamState(step=sh.replicated(mesh), mu=pshard, nu=pshard),
    err=None))
bshard = jax.tree_util.tree_map(
    lambda s: sh.batch_sharding(mesh, s, ("data",)), batch)
batch_sh = jax.device_put(batch, bshard)
with mesh:
    _, m = jax.jit(step)(state_sh, batch_sh)
np.testing.assert_allclose(float(m["loss"]), float(m_ref["loss"]), rtol=2e-2)
print("OK", float(m["loss"]), float(m_ref["loss"]))
""".replace("SRC", str(REPO / "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_train_state_is_born_sharded():
    """init_sharded_train_state gives every device only its shards of the
    parameters and Adam moments, with the values of the unsharded init."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np, sys
sys.path.insert(0, "SRC")
from repro.configs import get_reduced_config
from repro.configs.base import TrainConfig
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.train.loop import init_sharded_train_state, init_train_state

cfg = get_reduced_config("olmo-1b")
model = build_model(cfg)
tc = TrainConfig()
key = jax.random.PRNGKey(0)
want = init_train_state(model.init(key), tc)
state = init_sharded_train_state(model, tc, make_mesh((2, 2), ("data", "model")), key)
emb = state.params["embed"]
assert len(emb.addressable_shards) == 4, emb.sharding
assert all(s.data.size == emb.size // 4 for s in emb.addressable_shards), emb.sharding
assert state.opt.mu["embed"].sharding == emb.sharding
for a, b in zip(jax.tree_util.tree_leaves(state), jax.tree_util.tree_leaves(want)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("OK")
""".replace("SRC", str(REPO / "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout
