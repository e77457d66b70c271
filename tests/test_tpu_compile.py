"""Compile the device programs of the main path for a TPU v5e, without one.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests refuse what the chip's compiler
would refuse (too much fast memory, programs that do not fit the device)
at no chip time.  Nothing runs: results are checked by the differential
tests, and on the chip by ``chip_smoke.py``.

The topology is described inside a module fixture, never while a module
is imported, so every pytest-xdist worker collects the same tests and only
the worker given this file loads the TPU library.  While these tests run,
JAX's ambient mesh is one of the described chip, so a CPU mesh that an
earlier test of the same worker installed does not apply.  The persistent
compile cache is off around these compiles: an executable for a described
chip is written to it but cannot be read back on the CPU.
"""
import dataclasses
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.core.topology import make_topology
from repro.kernels.slot_alloc.fused import fused_prepare_program
from repro.models import make_model

HBM_BYTES = 16e9            # one TPU v5e
BATCH = 256                 # one CCU batch on the paper mesh
N_SLOTS = 16
MESH = make_topology()      # the paper's 8x8x4 bank mesh
N_PORTS = 7


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    env = pytest.MonkeyPatch()
    env.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        env.undo()
        jax.config.update("jax_enable_compilation_cache", cache_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    chip = topo.devices[0]
    with jax.sharding.set_mesh(Mesh(np.asarray([chip]), ("chip",))):
        yield SingleDeviceSharding(chip)
    env.undo()
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Compile for the described chip: (HLO text, total device bytes)."""
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return compiled.as_text(), total


def test_fused_prepare_program_compiles(one_chip):
    n = MESH.n_nodes
    req = _spec(one_chip, (BATCH,), jnp.int32)
    text, total = _compile(
        partial(fused_prepare_program, mesh=MESH, n_slots=N_SLOTS),
        _spec(one_chip, (n, N_PORTS), jnp.uint32), req, req, req)
    assert "tpu_custom_call" not in text
    assert total < HBM_BYTES


def test_served_decode_step_fits_one_chip(one_chip):
    """The decode step ``chip_smoke.py`` serves: qwen1.5-4b at published
    widths, depth cut to 16 layers, batch 4, on the Engine's serving copy
    of the weights.  The chip's compiler converts no stacked layer weight
    and no embedding table in it."""
    cfg = dataclasses.replace(get_config("qwen1.5-4b"), n_layers=16)
    model = make_model(cfg)

    def on_chip(tree):
        return jax.tree.map(
            lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    key = jax.ShapeDtypeStruct((2,), jnp.uint32)   # a raw PRNG key
    raw = jax.eval_shape(model.init, key)
    served = jax.eval_shape(model.serving_params, raw)
    caches = on_chip(jax.eval_shape(lambda: model.init_caches(4, 56)))
    text, total = _compile(model.decode_step, on_chip(served),
                           _spec(one_chip, (4, 1), jnp.int32), caches,
                           _spec(one_chip, (), jnp.int32))
    # The engine also holds the float32 leaves its copy casts.
    held = sum(a.size * a.dtype.itemsize for a, s in
               zip(jax.tree.leaves(raw), jax.tree.leaves(served))
               if a.dtype != s.dtype)
    assert total + held < HBM_BYTES
    weights = [raw["embed"]["embedding"].shape] + [
        a.shape for a in jax.tree.leaves(raw["stack"]) if a.ndim > 2]
    converts = [ln for ln in text.splitlines() if "convert" in ln]
    for shape in weights:
        out = re.compile(rf"= \w+\[{','.join(map(str, shape))}\]")
        assert not any(out.search(ln) for ln in converts), shape


def test_served_deepseek_v2_step_fits_one_chip(one_chip):
    """The decode step of ``serve.dsv2.chat128``: DeepSeek-V2 at published
    widths, one dense and four expert layers holding experts 0-7 of 160,
    an eighth of the vocabulary, 128 rows of 200 positions of latent
    cache, on the Engine's serving copy with its counters.  With the
    float32 leaves the copy casts it fits the chip, and the chip's
    compiler converts no weight in it."""
    cfg = dataclasses.replace(get_config("deepseek-v2-236b-a21b"),
                              n_layers=5, experts_held=(0, 8), vocab=12_800)
    model = make_model(cfg)

    def on_chip(tree):
        return jax.tree.map(
            lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    raw = jax.eval_shape(model.init, key)
    served = jax.eval_shape(model.serving_params, raw)
    caches = on_chip(jax.eval_shape(lambda: model.init_caches(128, 200)))
    text, total = _compile(model.decode_step_counted, on_chip(served),
                           _spec(one_chip, (128, 1), jnp.int32), caches,
                           _spec(one_chip, (), jnp.int32),
                           on_chip(jax.eval_shape(model.init_counts)))
    held = sum(a.size * a.dtype.itemsize for a, s in
               zip(jax.tree.leaves(raw), jax.tree.leaves(served))
               if a.dtype != s.dtype)
    assert total + held < HBM_BYTES, (total, held)
    weights = [raw["embed"]["embedding"].shape] + [
        a.shape for a in jax.tree.leaves(raw["stack"]) if a.ndim > 2]
    converts = [ln for ln in text.splitlines() if "convert" in ln]
    for shape in weights:
        out = re.compile(rf"= \w+\[{','.join(map(str, shape))}\]")
        assert not any(out.search(ln) for ln in converts), shape
