"""Compile the main path's Pallas kernels and executor walk for a described
TPU v5e chip, with no chip attached.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: i64 block indices under x64, tiles that overflow the 16 MiB
scoped-VMEM budget, minutes-long Mosaic compiles. These tests lower at the
sizes the executor really produces, under x64 (the protocol always runs
with it on), and check that the Pallas kernel is in the compiled program
and that no 64-bit integer op leaked into it.
"""

import os
import re
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import PrivacyConfig
from repro.core.gc_exec import LevelExecutor
from repro.core.netlist import compile_level_plan
from repro.core.protocol import PiTProtocol
from repro.kernels.level_eval.level_eval import (
    eval_level_pallas,
    garble_level_pallas,
)

#: a kernel compiles in about a second; the packed (G, 4) layout took
#: minutes, which is what this bound guards against
COMPILE_BOUND_S = 60.0


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off:
    described-device compiles can be written to it but never read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def gelu_net():
    """The GELU netlist the paper's protocol garbles (t=40 bits, f=7)."""
    p = PiTProtocol(PrivacyConfig(he_t_bits=40, frac_bits=7))
    return p.activation_net("gelu", 2 * p.frac)


def _u32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


def _compile(fn, *args):
    assert jax.config.jax_enable_x64
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    dt = time.perf_counter() - t0
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not re.findall(r"\b[su]64\[", text), "64-bit integer op on chip"
    assert dt < COMPILE_BOUND_S, f"compile took {dt:.1f}s"
    return compiled


@pytest.mark.parametrize("lane", ["eval", "garble"])
def test_level_kernel_compiles_at_executor_block(lane, gelu_net, one_chip):
    """The fused level kernels at the gate count one throughput-regime
    chunk hands them (GELU, I=4096): eval takes the AND+free block,
    garble the AND block alone."""
    I = 4096
    plan = compile_level_plan(gelu_net, instances=I, garbling=lane == "garble")
    if lane == "eval":
        g = (plan.and_width + plan.free_width) * I
        planes = [_u32(one_chip, 4, g)] * 4
        _compile(eval_level_pallas, _u32(one_chip, g), *planes,
                 _u32(one_chip, g))
    else:
        g = plan.and_width * I
        planes = [_u32(one_chip, 4, g)] * 3
        _compile(garble_level_pallas, _u32(one_chip, g), *planes,
                 _u32(one_chip, g))


@pytest.mark.parametrize("lane", ["eval", "garble"])
@pytest.mark.parametrize("instances", [1, 4096])
def test_executor_walk_compiles(instances, lane, gelu_net, one_chip):
    """The whole jitted walk (scan, gathers, prefetch, fused kernel) for
    the GELU netlist in both instance regimes, with the impl a chip
    resolves ``auto`` to."""
    I = instances
    garbling = lane == "garble"
    plan = compile_level_plan(gelu_net, instances=I, garbling=garbling)
    exe = LevelExecutor(plan, I, "pallas")
    assert exe.prefetch
    labels = _u32(one_chip, I, exe.n_src, 4)
    if garbling:
        _compile(exe._garble, labels, _u32(one_chip, I, 4))
    else:
        _compile(exe._eval, labels,
                 _u32(one_chip, I, max(plan.n_and, 1), 2, 4))
