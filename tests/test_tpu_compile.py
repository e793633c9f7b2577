"""Compile the SVD-path Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler, installed alongside jax, compiles each
kernel for a chip that is described, not attached, at the widths the
main path uses — the paper matrix's column blocks (M=544 after padding,
W=21,363 columns per block at D=8), a 2,048-row streamed batch, the
randomized sketch, and top-k serving over a 65,536-item universe.  A
kernel that asks for more VMEM than the chip has is refused here, which
interpret mode never shows.  Each compiled program must contain the
kernel as a ``tpu_custom_call``.

The topology is described inside a fixture (never at import), so every
pytest worker collects the same tests and only the worker that runs
this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_compile(one_chip, monkeypatch):
    """compile(fn, *(shape, dtype)) -> compiled text, for the described
    chip, with the kernels forced to compiled Pallas and the persistent
    compilation cache off (a chip-less compile cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def compile_text(fn, *specs):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in specs]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_text
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


F32, I32, I8 = jnp.float32, jnp.int32, jnp.int8


def _blockgram():
    return ops.blockgram, [((544, 21_504), F32)]


def _sparse_gram(m, c, k):
    def case():
        return (lambda r, v: ops.sparse_gram(r, v, m),
                [((c, k), I32), ((c, k), F32)])
    return case


def _sketch_panel():
    return ops.sketch_panel, [((24, 1024), F32), ((8192, 8), I32),
                              ((8192, 8), F32)]


def _topk_f32():
    return (lambda q, v: ops.topk_score(q, v, 10),
            [((8, 128), F32), ((65_536, 128), F32)])


def _topk_int8():
    return (lambda q, v, s: ops.topk_score(q, v, 10, scale=s),
            [((8, 128), F32), ((65_536, 128), I8), ((65_536,), F32)])


@pytest.mark.parametrize("case", [
    pytest.param(_blockgram, id="blockgram-M544-N21504"),
    pytest.param(_sparse_gram(544, 5_376, 8), id="sparse_gram-M544"),
    pytest.param(_sparse_gram(2048, 8_192, 16), id="sparse_gram-M2048"),
    pytest.param(_sketch_panel, id="sketch_panel-L24-M1024"),
    pytest.param(_topk_f32, id="topk_score-f32-B8-N65536"),
    pytest.param(_topk_int8, id="topk_score-int8-B8-N65536"),
])
def test_kernel_compiles_for_v5e(chip_compile, case):
    fn, specs = case()
    assert "tpu_custom_call" in chip_compile(fn, *specs)
