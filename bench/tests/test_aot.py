"""Each cell's KB call and decode step at the timed shapes, compiled ahead
of time for a TPU v5e described (not attached): what the chip's compiler
would refuse, and whether the program fits its 16 GB."""
import json

import pytest

from bench.tests.helpers import HERE

ROOT = HERE.parents[1]
HBM = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def cell_shapes():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    out = []
    for w in bm["workloads"]:
        cfg = json.loads((ROOT / next(c["file"] for c in bm["configs"]
                                      if c["name"] == w["config"])).read_text())
        mix = json.loads((ROOT / "bench" / "traffic" /
                          f"{w['traffic']}.json").read_text())
        out.append((w["name"], cfg, mix["clients"]))
    return out


@pytest.mark.parametrize("name,cfg,clients", cell_shapes(),
                         ids=[c[0] for c in cell_shapes()])
def test_kb_call_compiles_for_v5e(name, cfg, clients, one_chip):
    import jax
    import jax.numpy as jnp
    from repro.kernels.dense_topk import dense_topk_pallas
    kb = cfg["kb"]
    k = cfg["serving"].get("knn_k", 8) if cfg["workload"] == "knnlm" else 20
    keys = jax.ShapeDtypeStruct((kb["rows"], kb["dim"]), jnp.float32,
                                sharding=one_chip)
    for B in (1, clients * (cfg["serving"]["max_stride"] + 1)):
        q = jax.ShapeDtypeStruct((B, kb["dim"]), jnp.float32, sharding=one_chip)
        # the program's KB call on a TPU (its op asks the default backend,
        # which here is the CPU, so the kernel is called as the chip would)
        c = jax.jit(lambda q, kb_: dense_topk_pallas(q, kb_, k)).lower(
            q, keys).compile()
        assert "tpu_custom_call" in c.as_text()
        m = c.memory_analysis()
        assert m.argument_size_in_bytes + m.temp_size_in_bytes < HBM


@pytest.mark.parametrize("name,cfg,clients", cell_shapes(),
                         ids=[c[0] for c in cell_shapes()])
def test_decode_step_compiles_for_v5e(name, cfg, clients, one_chip):
    import dataclasses

    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models.model import build_model
    from repro.serving.engine import lm_programs
    from bench.stack import MODEL_KEYS
    mcfg = dataclasses.replace(get_config(cfg["arch"]),
                               **{k: cfg[k] for k in MODEL_KEYS if k in cfg})
    model = build_model(mcfg)
    W = cfg["serving"]["cache_window"]
    put = lambda t: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), t)
    params = put(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    state = put(jax.eval_shape(lambda: model.init_decode_state(clients, W)))
    vec = jax.ShapeDtypeStruct((clients,), jnp.int32, sharding=one_chip)
    decode, _ = lm_programs(model, W, None)
    c = decode.lower(params, state, vec, vec).compile()
    m = c.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < HBM
