"""Distribution layer: sharding-rule properties, sharded retrieval, dry-run smoke."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed.sharding import _sanitize, param_specs, state_specs
from repro.launch.mesh import make_local_mesh
from repro.models.model import build_model

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _specs_for(arch, mesh, **kw):
    model = build_model(get_config(arch))
    ps = jax.eval_shape(lambda k: model.init(k, jnp.bfloat16), jax.random.PRNGKey(0))
    return ps, param_specs(ps, mesh, **kw)


def _sharded_fraction(params, specs, sizes):
    tot = tot_sh = 0
    leaves_p = jax.tree.leaves(params)
    leaves_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    for leaf, spec in zip(leaves_p, leaves_s):
        nbytes = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        div = 1
        for e in spec:
            if e is None:
                continue
            for a in (e if isinstance(e, tuple) else (e,)):
                div *= sizes[a]
        tot += nbytes
        tot_sh += nbytes // div
    return tot, tot_sh


def test_sanitize_drops_nondivisible():
    sizes = {"data": 16, "model": 16}
    assert _sanitize(P("model"), (8,), sizes) == P(None)        # 8 % 16 != 0
    assert _sanitize(P("model"), (32,), sizes) == P("model")
    assert _sanitize(P(("data", "model")), (256,), sizes) == P(("data", "model"))
    assert _sanitize(P("pod"), (32,), sizes) == P(None)          # axis absent


@pytest.mark.parametrize("arch,max_ratio", [
    ("kimi-k2-1t-a32b", 1.05), ("qwen1.5-110b", 1.05),
    ("command-r-plus-104b", 1.05), ("jamba-v0.1-52b", 1.10),
])
def test_param_sharding_near_ideal(arch, max_ratio):
    """Per-device parameter bytes within a few % of total/256 on the 16x16 mesh."""
    import jax.sharding
    devs = np.array(jax.devices() * 256)[:256].reshape(16, 16)
    mesh = jax.sharding.Mesh(devs, ("data", "model"))
    params, specs = _specs_for(arch, mesh)
    tot, tot_sh = _sharded_fraction(params, specs,
                                    {"data": 16, "model": 16})
    assert tot_sh <= (tot / 256) * max_ratio, \
        f"{arch}: {tot_sh/1e9:.2f}GB/device vs ideal {tot/256/1e9:.2f}GB"


def test_tp_false_replicates_weights():
    import jax.sharding
    devs = np.array(jax.devices() * 256)[:256].reshape(16, 16)
    mesh = jax.sharding.Mesh(devs, ("data", "model"))
    params, specs = _specs_for("xlstm-350m", mesh, fsdp=False, tp=False)
    for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
        assert all(e is None for e in s), s


def test_state_specs_kv_modes():
    import jax.sharding
    devs = np.array(jax.devices() * 256)[:256].reshape(16, 16)
    mesh = jax.sharding.Mesh(devs, ("data", "model"))
    model = build_model(get_config("llama3.2-1b"))
    st = jax.eval_shape(lambda: model.init_decode_state_stacked(128, 32768,
                                                                jnp.bfloat16))
    for mode, want_axis in [("replicated", None), ("window", "model")]:
        specs = state_specs(st, mesh, 128, kv_shard=mode)
        flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        k_specs = [s for s in flat if len(s) == 5]  # stacked (rep,B,W,KV,hd)
        assert k_specs, "no stacked KV specs found"
        for s in k_specs:
            assert s[2] == want_axis, (mode, s)


def test_sharded_retrieval_matches_ref():
    from repro.kernels.ref import dense_topk_ref
    from repro.retrieval.sharded import sharded_dense_topk
    mesh = make_local_mesh()
    kq, kk = jax.random.split(jax.random.PRNGKey(0))
    q = jax.random.normal(kq, (4, 32))
    kb = jax.random.normal(kk, (1000, 32))
    with jax.set_mesh(mesh):
        s1, g1 = sharded_dense_topk(q, kb, 8, mesh, axis="model")
    s2, g2 = dense_topk_ref(q, kb, 8)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-5)
    assert np.array_equal(np.asarray(g1), np.asarray(g2))


@pytest.mark.slow
def test_dryrun_pair_subprocess():
    """One cheap (arch x shape) pair lowers+compiles on the 512-device platform."""
    code = (
        "import os;"
        "os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=512';"
        "from repro.launch.dryrun import dryrun_pair;"
        "r = dryrun_pair('xlstm-350m','long_500k',verbose=False);"
        "print('DRYRUN_OK' if r['ok'] else r['error'])"
    )
    env = dict(os.environ, PYTHONPATH=SRC)
    env["JAX_PLATFORMS"] = "cpu"      # a test child never reaches for a chip
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env)
    assert "DRYRUN_OK" in out.stdout, out.stdout + out.stderr
