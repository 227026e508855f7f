"""Compiles for a described (not attached) v5e chip at full size, plus
the CPU checks of the device mesh and the compile-cache helper.

The compiles use the TPU compiler installed with JAX: a graph kernel
that Mosaic refuses, or a pass that does not fit one chip's 16 GiB,
fails here without a chip.  The topology is described inside a
fixture, never while the module is imported: only one process at a
time may load the TPU library, and every test worker imports this
file.  The persistent compilation cache is off while these compiles
run: an entry compiled for a described chip cannot be read back.

Shapes are those of host-mode rounds over the Graph500 graph
``G.rmat(22, 16, seed=0)`` (V = 2**22, E = 65,244,445): its degree bins
hold 1,382,458 small (deg <= 8), 517,420 medium (<= 128) and 100,364
large (< 1024) vertices and 9,109 huge ones carrying 22,719,251 edges;
a round over a whole bin runs at the power-of-two bucket above it.
"""
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import compile_cache
from repro.core import gluon
from repro.core import operators as ops
from repro.core.balancer import BalancerConfig, get_executor
from repro.core.graph import Graph
from repro.kernels import edge_lb, merge_path, twc_gather

V = 1 << 22
E = 65_244_445
HBM_BYTES = 16 * 2**30
CFG = BalancerConfig()


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 host, with the persistent
    compilation cache off."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(lowered):
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert used <= HBM_BYTES, f"{used / 2**30:.2f} GiB"
    return compiled.as_text()


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (name, bin members, bin width) of the three degree bins, and the LB
# path: the huge bin (alb) or the whole frontier (merge_path)
_TWC_BINS = [("small", 1 << 21, 8), ("medium", 1 << 19, 128),
             ("large", 1 << 17, 1024)]


@pytest.mark.parametrize("name,members,width", _TWC_BINS)
def test_twc_bin_map_compiles(one_chip, name, members, width):
    vec = _spec(one_chip, (members,))
    text = _compile(twc_gather.twc_bin_map.lower(
        vec, vec, vec, vec, width=width, chunk=_spec(one_chip, ())))
    assert "tpu_custom_call" in text


def test_edge_lb_map_compiles(one_chip):
    h = _spec(one_chip, (1 << 14,))
    text = _compile(edge_lb.edge_lb_map.lower(
        h, h, h, _spec(one_chip, ()), 1 << 25,
        tile_edges=CFG.lb_tile_edges))
    assert "tpu_custom_call" in text


def test_merge_path_map_compiles(one_chip):
    h = _spec(one_chip, (1 << 21,))
    text = _compile(merge_path.merge_path_map.lower(
        h, h, _spec(one_chip, ()), 1 << 26, tile_edges=CFG.lb_tile_edges))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch", [1, 8])
def test_xla_host_passes_compile(one_chip, batch):
    """The xla backend's large-bin pass and huge-bin LB pass, for one
    query and for the service's eight slots."""
    g = Graph(_spec(one_chip, (V + 1,)), _spec(one_chip, (E,)),
              _spec(one_chip, (E,)))
    labels = _spec(one_chip, (batch, V))
    fmask = _spec(one_chip, (batch, V), jnp.bool_)
    ex = get_executor("xla")
    n = 1 << 17
    bins = _spec(one_chip, (n,))
    _compile(ex.bin_host.lower(g, labels, labels, fmask, bins, bins, bins,
                               CFG.large_width, ops.SSSP_RELAX, 0))
    h = _spec(one_chip, (1 << 14,))
    _compile(ex.lb_host.lower(g, labels, labels, fmask, h, h, h,
                              _spec(one_chip, ()), 1 << 25,
                              ops.SSSP_RELAX, CFG.distribution,
                              CFG.num_tiles, CFG.lb_tile_edges))


# ---- CPU checks ------------------------------------------------------------

def test_device_mesh_refuses_missing_devices():
    have = len(jax.devices())
    assert gluon.device_mesh(have).devices.size == have
    with pytest.raises(ValueError, match="needs"):
        gluon.device_mesh(have + 1)


def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv(compile_cache.ENV_VAR)
    root = Path(__file__).resolve().parents[1]
    assert compile_cache.compile_cache_dir() == str(root / ".jax_cache")


@pytest.mark.parametrize("from_env", [True, False])
def test_enable_compile_cache_sets_only_the_fixed_path(monkeypatch,
                                                       tmp_path, from_env):
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        if from_env:
            assert path == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert path == str(compile_cache.DEFAULT_DIR)
            assert jax.config.jax_compilation_cache_dir == path
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
