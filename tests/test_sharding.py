"""Sharding rules + a small-mesh pjit train step (subprocess: needs >1 host
device, while the main pytest process keeps 1 device per the assignment)."""

import subprocess
import sys
import textwrap

import jax

from repro.configs import get_config
from repro.parallel.sharding import param_spec, param_specs
from repro.models import init_params


def test_param_rules_cover_every_leaf():
    import jax.numpy as jnp
    for arch in ("glm4_9b", "llama4_maverick", "xlstm_125m", "hymba_1_5b"):
        cfg = get_config(arch, smoke=True)
        p = jax.eval_shape(lambda k: init_params(cfg, k),
                           jax.ShapeDtypeStruct((2,), jnp.uint32))
        specs = param_specs(p)
        flat_p = jax.tree_util.tree_leaves(p)
        flat_s = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        assert len(flat_p) == len(flat_s)
        for leaf, spec in zip(flat_p, flat_s):
            assert len([a for a in spec if a is not None]) <= leaf.ndim


def test_big_matrices_are_2d_sharded():
    assert tuple(param_spec("blocks/0/attn/wq")) == (None, "data", "model")
    assert tuple(param_spec("blocks/0/mlp/wd")) == (None, "model", "data")
    assert tuple(param_spec("blocks/1/moe/wg")) == (None, "model", "data", None)
    assert tuple(param_spec("embed")) == ("model", "data")
    assert tuple(param_spec("blocks/0/ln1/scale")) in ((), (None,))


_SMALL_MESH = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import sys; sys.path.insert(0, "src")
    from jax.sharding import NamedSharding, PartitionSpec as P
    import dataclasses
    from repro.configs import get_config
    from repro.launch.mesh import make_debug_mesh
    from repro.models import model as M
    from repro.models import init_params
    from repro.parallel import sharding as S
    from repro.training import optimizer as O

    mesh = make_debug_mesh(4, 2)
    cfg = dataclasses.replace(get_config("yi_6b", smoke=True),
                              batch_axes=("data",))
    opt_cfg = O.OptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    params = init_params(cfg, jax.random.PRNGKey(0))
    p_shard = S.param_shardings(params, mesh)
    params = jax.tree_util.tree_map(jax.device_put, params, p_shard)
    opt_state = O.init(params, opt_cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0,
                                cfg.vocab_size, jnp.int32)
    tokens = jax.device_put(tokens, NamedSharding(mesh, P("data", None)))

    def step(p, o, b):
        (loss, mets), grads = jax.value_and_grad(M.loss_fn, has_aux=True)(p, b, cfg)
        p, o, _ = O.apply_updates(grads=grads, params=p, state=o, cfg=opt_cfg)
        return p, o, loss

    with mesh:
        p2, o2, loss = jax.jit(step)(params, opt_state, {"tokens": tokens})
    assert jnp.isfinite(loss), loss
    # distributed result == single-device result
    p_host = jax.device_get(params)
    loss_ref = M.loss_fn(p_host, {"tokens": jax.device_get(tokens)}, cfg)[0]
    assert abs(float(loss) - float(loss_ref)) < 1e-3, (loss, loss_ref)
    print("MESH_OK", float(loss))
""")


def test_small_mesh_train_step_matches_single_device():
    r = subprocess.run([sys.executable, "-c", _SMALL_MESH],
                       capture_output=True, text=True, timeout=600)
    assert "MESH_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]


_COMPRESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    import sys; sys.path.insert(0, "src")
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.parallel.collectives import cross_pod_grad_reduce

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    g = {"w": jnp.linspace(-1.0, 1.0, 64).reshape(8, 8)}
    e = {"w": jnp.zeros((8, 8))}
    out, err = cross_pod_grad_reduce(g, e, mesh)
    # identical per-pod grads -> mean == original, int8 quantization error small
    ref = np.asarray(g["w"])
    got = np.asarray(out["w"])
    assert np.max(np.abs(got - ref)) < 1.5 / 127, np.max(np.abs(got - ref))
    # error feedback captured the residual
    assert np.max(np.abs(np.asarray(err["w"]))) > 0
    print("COMPRESS_OK")
""")


def test_int8_error_feedback_grad_reduce():
    r = subprocess.run([sys.executable, "-c", _COMPRESS],
                       capture_output=True, text=True, timeout=600)
    assert "COMPRESS_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]


_REMESH = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    import sys; sys.path.insert(0, "src")
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint import checkpointer as C

    d = tempfile.mkdtemp()
    # save under a 4x2 mesh layout
    mesh_a = jax.make_mesh((4, 2), ("data", "model"))
    w = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                       NamedSharding(mesh_a, P("data", "model")))
    C.save(d, 1, {"w": w})
    # restore under a 2x4 mesh (elastic re-mesh)
    mesh_b = jax.make_mesh((2, 4), ("data", "model"))
    sh = {"w": NamedSharding(mesh_b, P("data", "model"))}
    step, state = C.restore(d, {"w": w}, shardings=sh)
    assert state["w"].sharding == sh["w"]
    assert np.array_equal(np.asarray(state["w"]), np.arange(64.0).reshape(8, 8))
    print("REMESH_OK")
""")


def test_elastic_remesh_across_mesh_shapes():
    r = subprocess.run([sys.executable, "-c", _REMESH],
                       capture_output=True, text=True, timeout=600)
    assert "REMESH_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
