"""Compiles of the main accelerator paths for a described TPU v5e chip.

Nothing runs here: each test lowers and compiles at the real size for
one chip of a `v5e:2x2` topology, which the installed TPU compiler can
describe without the chip. That catches what interpret mode on the CPU
cannot (tiling, fast-memory limits, programs that do not fit in HBM).

The topology is described inside a module fixture, never at import, so
that only the test worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed, or no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_aging_kernel_compiles_at_fleet_width(one_chip):
    """The Pallas aging kernel at 200 machines x 40 cores (8,192 lanes
    after padding) compiles to a TPU custom call."""
    from repro.core.aging import DEFAULT_PARAMS
    from repro.kernels.aging_update.aging_update import aging_update

    x = jax.ShapeDtypeStruct((8192,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda *a: aging_update(*a, DEFAULT_PARAMS, interpret=False)
    ).lower(x, x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fleet_macro_scan_compiles_with_kernel(one_chip, monkeypatch):
    """The fleet engine's whole jitted macro scan for 200 machines x 1
    simulated hour, resolved as on a TPU (compiled Pallas kernel)."""
    from repro.sim import ExperimentConfig
    from repro.sim.fleetsim import FleetEngine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ExperimentConfig(n_prompt=45, n_token=155, rate_rps=545.0,
                           duration_s=3600.0).with_engine("fleet",
                                                          backend="jax")
    eng = FleetEngine(cfg)
    assert (eng.use_kernel, eng.interpret) == (True, False)
    # Only shapes reach the compiler: an empty trace bins to the same
    # (steps, 3) input as the real one.
    scan, state, xs = eng._build_jax(eng._bin_trace([]))
    assert xs[0].shape[0] == 3600
    compiled = scan.lower(*_on(one_chip, (state, xs))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


@pytest.mark.parametrize("phase", ["init", "decode", "prefill"])
def test_minicpm3_4b_serving_step_fits_one_chip(one_chip, phase):
    """minicpm3-4b at its published widths and depth: the jitted random
    init, the engine's decode step at batch 8 x 2,048 cache, and one
    1,024-token prefill into a 2,048 cache, each fit one chip's HBM."""
    from repro.configs import get_config
    from repro.models import Model

    model = Model(get_config("minicpm3-4b"))
    params = model.abstract_params()
    if phase == "init":
        args = (jax.ShapeDtypeStruct((), jax.random.key(0).dtype),)
        fn = jax.jit(model.init)
    elif phase == "decode":
        cache = jax.eval_shape(
            lambda p, t: model.prefill(p, t, None, max_len=2048)[1],
            params, jax.ShapeDtypeStruct((8, 1), jnp.int32))
        cache["pos"] = jax.ShapeDtypeStruct((8,), jnp.int32)
        args = (params, cache, jax.ShapeDtypeStruct((8, 1), jnp.int32))
        fn = jax.jit(model.decode_step)
    else:
        args = (params, jax.ShapeDtypeStruct((1, 1024), jnp.int32))
        fn = jax.jit(lambda p, t: model.prefill(p, t, None, max_len=2048))
    compiled = fn.lower(*_on(one_chip, args)).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_deepseek_v2_lite_serving_step_fits_one_chip(one_chip, phase,
                                                     monkeypatch):
    """deepseek-v2-lite as the benchmark serves it (all 27 layers at the
    published widths, 16 of 64 routed experts held): the decode step at
    16 slots x 4,096 cache and one 1,024-token prefill into a 4,096
    cache fit one chip's HBM, with the grouped matmul compiled as the
    Megablox kernel."""
    from repro.configs import get_config
    from repro.models import Model

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = Model(get_config("deepseek-v2-lite"))
    params = model.abstract_params()
    if phase == "decode":
        cache = jax.eval_shape(
            lambda p, t: model.prefill(p, t, None, max_len=4096)[1],
            params, jax.ShapeDtypeStruct((16, 1), jnp.int32))
        cache["pos"] = jax.ShapeDtypeStruct((16,), jnp.int32)
        args = (params, cache, jax.ShapeDtypeStruct((16, 1), jnp.int32),
                jax.ShapeDtypeStruct((16,), jnp.bool_))
        fn = jax.jit(model.decode_step)
    else:
        args = (params, jax.ShapeDtypeStruct((1, 1024), jnp.int32))
        fn = jax.jit(lambda p, t: model.prefill(p, t, None, max_len=4096))
    compiled = fn.lower(*_on(one_chip, args)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < V5E_HBM_BYTES
