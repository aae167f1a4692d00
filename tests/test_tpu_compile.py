"""Ahead-of-time compiles of the serving path's retrieval kernels for a
described TPU v5e (2x2) topology — no chip attached.

Interpret mode (every other kernel test) cannot see what the chip's Mosaic
compiler refuses: gathers it cannot lower, blocks off the (8, 128) tiling,
DMA slices not aligned to it, matmul forms it cannot parse, more VMEM than
a kernel may use. These tests compile each kernel at serving widths and
assert that the program holds a ``tpu_custom_call`` — the kernel itself,
not a fallback. The kernels are called with ``interpret=False`` directly:
``kernels.ops`` picks interpret mode from the default backend, which is the
CPU here.

The topology is described inside a module fixture (never at import) so that
under pytest-xdist only the worker given this file loads the TPU compiler,
and every test here skips when no topology can be described.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dense_topk as K

D, N, TOPK, C = 768, 1 << 20, 20, 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """ShapeDtypeStruct factory placed on the first described chip."""
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])
    return lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_cases(B, S):
    f32, i8, i32 = jnp.float32, jnp.int8, jnp.int32
    q = S((B, D), f32)
    return {
        "dense": (lambda q, kb: K.dense_topk_pallas(q, kb, TOPK),
                  (q, S((N, D), f32))),
        "quant": (lambda q, kb, s: K.quant_topk_pallas(q, kb, s, TOPK),
                  (q, S((N, D), i8), S((N,), f32))),
        "fused": (lambda q, kb, c: K.fused_gathered_topk_pallas(q, kb, c,
                                                                TOPK),
                  (q, S((N, D), f32), S((B, C), i32))),
        "quant_fused": (lambda q, kb, s, c: K.quant_fused_gathered_topk_pallas(
                            q, kb, s, c, TOPK),
                        (q, S((N, D), i8), S((N,), f32), S((B, C), i32))),
        "gathered": (lambda q, e, c: K.gathered_topk_pallas(q, e, c, TOPK),
                     (q, S((B, C, D), f32), S((B, C), i32))),
        "quant_gathered": (lambda q, e, s, c: K.quant_gathered_topk_pallas(
                               q, e, s, c, TOPK),
                           (q, S((B, C, D), i8), S((B, C), f32),
                            S((B, C), i32))),
    }


@pytest.mark.parametrize("B", [4, 16])
@pytest.mark.parametrize("kernel", ["dense", "quant", "fused", "quant_fused",
                                    "gathered", "quant_gathered"])
def test_retrieval_kernel_compiles_for_v5e(shape, kernel, B):
    fn, args = _kernel_cases(B, shape)[kernel]
    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.mark.parametrize("quant", [False, True])
def test_fused_gather_compiles_at_ivf_probe_width(shape, quant):
    """A 500k-doc IVF index probes ~62.5k candidates per query: the id
    matrix must never have to fit SMEM whole (1 MiB on v5e), only the
    current id tile."""
    B, Cw, n = 16, 62_500, 500_000
    q, cand = shape((B, D), jnp.float32), shape((B, Cw), jnp.int32)
    if quant:
        text = _compiled_text(
            lambda q, kb, s, c: K.quant_fused_gathered_topk_pallas(
                q, kb, s, c, TOPK),
            q, shape((n, D), jnp.int8), shape((n,), jnp.float32), cand)
    else:
        text = _compiled_text(
            lambda q, kb, c: K.fused_gathered_topk_pallas(q, kb, c, TOPK),
            q, shape((n, D), jnp.float32), cand)
    assert "tpu_custom_call" in text


def test_sharded_edr_scan_compiles_over_four_chips(topo):
    """The sharded EDR scan — per-shard scan plus ONE all-gather — over a
    4-chip mesh of the described devices, at a 4M x 768 KB."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.retrieval.sharded import sharded_dense_topk
    mesh = Mesh(np.asarray(topo.devices[:4]), ("data",))
    q = jax.ShapeDtypeStruct((8, D), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    kb = jax.ShapeDtypeStruct((4 << 20, D), jnp.float32,
                              sharding=NamedSharding(mesh, P("data", None)))

    def scan(q, kb):
        return sharded_dense_topk(q, kb, TOPK, mesh, axis="data")

    with jax.set_mesh(mesh):
        compiled = jax.jit(scan).lower(q, kb).compile()
    text = compiled.as_text()
    assert text.count("all-gather-start") + text.count("all-gather(") >= 1
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < (4 << 20) * D * 4 // 2   # the KB really is sharded


KERNEL_NAMES = {"dense": "dense_topk", "quant": "quant_topk",
                "fused": "fused_gathered_topk",
                "quant_fused": "quant_fused_gathered_topk",
                "gathered": "gathered_topk",
                "quant_gathered": "quant_gathered_topk"}


@pytest.mark.parametrize("kernel", sorted(KERNEL_NAMES))
def test_kernel_instruction_carries_its_name(shape, kernel):
    """A profiler trace names a kernel by its custom call's instruction
    (``dense_topk:dense_topk.1``): the ``name=`` of the ``pallas_call``
    sets it, whatever name scope the call sits in."""
    fn, args = _kernel_cases(4, shape)[kernel]
    text = _compiled_text(fn, *args)
    names = re.findall(r"%([\w.-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
                       text)
    assert names
    assert {n.rsplit(".", 1)[0] for n in names} == {KERNEL_NAMES[kernel]}


@pytest.mark.parametrize("kernel", ["dense", "quant"])
def test_exact_scan_reads_an_off_block_kb_in_place(shape, kernel):
    """At the KNN-LM cell's 806,461 x 1024 KB (off the 1024-row block) the
    exact scans read the resident KB as it is: the kernel sits in the
    ``kb_scan`` scope, no pad op copies the KB, and the program holds no
    temporary of the KB's size (a per-call pad held 3,305,143,808 bytes)."""
    n, d, kb_dt = 806_461, 1024, jnp.int8 if kernel == "quant" else jnp.float32
    q, kb = shape((8, d), jnp.float32), shape((n, d), kb_dt)
    if kernel == "quant":
        compiled = jax.jit(lambda q, kb, s: K.quant_topk_pallas(q, kb, s, 8)
                           ).lower(q, kb, shape((n,), jnp.float32)).compile()
    else:
        compiled = jax.jit(lambda q, kb: K.dense_topk_pallas(q, kb, 8)
                           ).lower(q, kb).compile()
    text = compiled.as_text()
    ops = dict(re.findall(r"%([\w.-]+) = [^\n]*op_name=\"([^\"]*)\"", text))
    assert any(f"kb_scan/{KERNEL_NAMES[kernel]}" in v
               and k.startswith(f"{KERNEL_NAMES[kernel]}.")
               for k, v in ops.items())
    pads = re.findall(r"%pad[\w.-]* = \w+\[([\d,]*)\]", text)
    assert not [p for p in pads if p.endswith(f",{d}")]
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20
